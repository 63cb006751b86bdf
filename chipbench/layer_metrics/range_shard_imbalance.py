"""``range_shard_imbalance``: how much fuller than the mean the fullest
shard came out of the range shuffles (the sample sort's splitters): the
program's rollup counters ``shuffle.range.shard_rows_max`` over
``shuffle.range.shard_rows_mean`` (``obs/trace.bump`` in ``_shuffle_many``,
from the counts the range shuffle fetches), less one, in percent. Both
counters sum over the process's queries, so the ratio is the mean query's.
``None`` where the program has no such counters (a commit from before
them)."""
from cylon_tpu.utils import tracing


def read(obs: dict):
    rollup = tracing.snapshot()
    mean = rollup.get("shuffle.range.shard_rows_mean", {}).get("rows", 0)
    if not mean:
        return None
    return 100.0 * (rollup["shuffle.range.shard_rows_max"]["rows"] / mean - 1.0)
