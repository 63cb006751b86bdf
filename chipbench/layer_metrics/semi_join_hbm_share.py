"""``semi_join_hbm_share``: the semi join's share of its HBM roofline. The
least time the chip's HBM could take for it
(``queries/tpch_q4.semi_least_bytes``: both sides' key ids and live masks
read once, the left side's hit mask written once, over the peak of
``peaks.json``) as a share of ``semi_join_ms``, the device time a query of
the stages ``join.semi`` and ``join.semi_mask``, first device. The least
bytes name the work and not the kernel, so the share reads the same
question whatever implements the operator; the query's ``least_bytes``
has computed them by now. ``None`` where the stages did not run or the
query is another."""
import sys

from chipbench.layer_metrics import semi_join_ms


def read(obs: dict):
    q4 = sys.modules.get("chipbench.queries.tpch_q4")
    least = q4._RUN.get("semi_least_bytes") if q4 is not None else None
    ms = semi_join_ms.read(obs)
    if least is None or not ms:
        return None
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
