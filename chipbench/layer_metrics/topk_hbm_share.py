"""``topk_hbm_share``: the top-k's share of its HBM roofline. The least
time the chip's HBM could take for it (``queries/tpch_q3.topk_least_bytes``:
the two key lanes of every group read once, the rows kept written once,
over the peak of ``peaks.json``) as a share of the device time a query of
the stage ``sort.topk``, first device. The number of groups is the
reference's, which the query's ``least_bytes`` has computed by now.
``None`` where the stage did not run or the query is another."""
import sys

from chipbench import stage_times


def read(obs: dict):
    q3 = sys.modules.get("chipbench.queries.tpch_q3")
    ref = q3._RUN.get("ref") if q3 is not None else None
    ms = stage_times.stage_ms(obs, "sort.topk")
    if ref is None or not ms:
        return None
    least = q3.topk_least_bytes(len(ref["groups"][q3.REVENUE]), ref["rows"])
    return 100.0 * least / obs["peaks"]["hbm_bytes_per_s"] / (ms / 1e3)
