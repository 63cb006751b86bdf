"""``hash_shard_imbalance``: how much fuller than the mean the fullest
shard came out of the hash shuffles: the program's rollup counters
``shuffle.hash.shard_rows_max`` over ``shuffle.hash.shard_rows_mean``
(``obs/trace.bump`` in ``_shuffle_many``, from the count matrix the host
fetches anyway, chosen after the semi filter's decision), less one, in
percent. Both counters sum over the process's hash shuffles (every call
from the first warm-up on is the cell's one query, both its tables), so the
ratio is the mean shuffle's, weighted by rows. ``None`` where the program
has no such counters (a commit from before them)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    mean = rollup.get("shuffle.hash.shard_rows_mean", {}).get("rows", 0)
    if not mean:
        return None
    return 100.0 * (rollup["shuffle.hash.shard_rows_max"]["rows"] / mean - 1.0)
