"""``groupby_partial_hbm_share``: the pre-combine's share of its HBM
roofline. The least time one chip's HBM could take for its part
(``queries/h2o_groupby.partial_least_bytes``: the key and the summed
columns read once and the shards' partial rows, by the program's own count
``groupby.precombine.rows_out`` a query, written once, DIVIDED BY THE
CELL'S CHIPS, since the trace reduction keeps the first chip's operations
and that chip holds one share of the rows; over the peak of
``peaks.json``) as a share of ``groupby_partial_ms``, the device time a
query of the stage ``groupby.partial``, first device. The least bytes name
the work and not the kernel. It cannot pass 100 (the stage reads every
column at least once) and reads well under 1: the stage is two sorts of
every slot with five to eight 32-bit operands riding, some forty passes
over the data, where the bound counts one. ``None`` where the stage did
not run, the program has no such counter, or the query is another."""
import sys

from chipbench.layer_metrics import groupby_partial_ms
from cylon_tpu.utils import tracing


def read(obs: dict):
    query = sys.modules.get("chipbench.queries.h2o_groupby")
    counter = tracing.snapshot().get("groupby.precombine.rows_out")
    ms = groupby_partial_ms.read(obs)
    if query is None or not counter or not counter.get("count") or not ms:
        return None
    least = query.partial_least_bytes(counter["rows"] / counter["count"])
    if least is None:
        return None
    chips = len(obs["trace"]["devices"])
    least_s = least / chips / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
