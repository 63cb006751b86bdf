"""``join_outer_emit_hbm_share``: the LEFT join's emit's share of its HBM
roofline. The least time one chip's HBM could take for its part of the
emit (:func:`emit_least_bytes`, over the peak of ``peaks.json``) as a
share of ``join_outer_emit_ms``, the device time a query of the stage
``join.emit``, first device. The least bytes name the work and not the
kernel: every output column written once (both sides' columns and a byte
of validity a right-side column), every left column and the probe's two
32-bit lanes a left row (first match, match count) read once, the build
table read once. The trace reduction keeps the first chip's operations,
and that chip writes one share of the result and reads one share of the
left side, but where the build side is replicated it reads the WHOLE of
it. The share cannot pass 100 (the stage writes every output column at
least once) and reads well under it: the gathers address every row on
its own. ``None`` where the stage did not run or the query is another."""
import sys

from chipbench.layer_metrics import join_outer_emit_ms


def emit_least_bytes(shapes: dict, chips: int) -> float:
    """Bytes ONE chip's emit cannot avoid moving through HBM."""
    probe_lanes = 8  # lo and cnt, int32 each, a left row
    return (
        shapes["out_rows"] * shapes["out_row"] / chips
        + shapes["left_rows"] * (shapes["left_row"] + probe_lanes) / chips
        + shapes["right_rows"] * shapes["right_row"]
    )


def read(obs: dict):
    query = sys.modules.get("chipbench.queries.h2o_join")
    ms = join_outer_emit_ms.read(obs)
    shapes = query.emit_shapes() if query is not None else None
    if not ms or not shapes:
        return None
    chips = len(obs["trace"]["devices"])
    least_s = emit_least_bytes(shapes, chips) / obs["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least_s / (ms / 1e3)
