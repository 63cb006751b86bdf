"""``dense_partial_hbm_share``: the dense aggregate's share of its HBM
roofline where each chip reduces its own rows. The least time one chip's
HBM could take for its part of the query (the bytes of the query's own
``least_bytes``, every input column read once and the result written
once, DIVIDED BY THE CELL'S CHIPS, since the trace reduction keeps the
first chip's operations and that chip holds one share of the rows; over
the peak of ``peaks.json``) as a share of the device time a query that
the stages ``groupby.dense_agg``, ``expr.eval`` and ``groupby.combine``
took, first device. The bound is HBM bytes: the float64 arithmetic is
emulated on the vector unit and has no published peak. It cannot pass
100%: the three stages read every half of every column the query reads
at least once (Q6 at scale factor 10: 1.92 GB, 2.3 ms). ``None`` where
none of the stages ran."""
from chipbench import stage_times

STAGES = ("groupby.dense_agg", "expr.eval", "groupby.combine")


def read(obs: dict):
    found = stage_times.split(obs)
    if found is None:
        return None
    ms = sum(found["stages_ms"].get(stage, 0.0) for stage in STAGES)
    if ms <= 0:
        return None
    chips = len(obs["trace"]["devices"])
    least_s = obs["least_bytes"] / chips / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
