"""``groupby_dense_hbm_share``: the dense group-by's share of its HBM
roofline. The least time the chip's HBM could take for the query (the
bytes of the query's own ``least_bytes``: every input column read once,
the result written once, over the peak of ``peaks.json``) as a share of
the device time a query that the stages ``groupby.dense_agg`` and
``expr.eval`` took, first device. The bound is HBM bytes: the float64
arithmetic is emulated on the vector unit and has no published peak.
``None`` where neither stage ran."""
from chipbench import stage_times

STAGES = ("groupby.dense_agg", "expr.eval")


def read(obs: dict):
    found = stage_times.split(obs)
    if found is None:
        return None
    ms = sum(found["stages_ms"].get(stage, 0.0) for stage in STAGES)
    if ms <= 0:
        return None
    least_s = obs["least_bytes"] / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
