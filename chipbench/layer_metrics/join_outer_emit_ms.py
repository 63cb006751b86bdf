"""``join_outer_emit_ms``: device self time a query of the operations whose
stage is ``join.emit`` in a cell whose join is a LEFT OUTER one (the
repeat, the left gather with the probe's lanes riding, the right gather by
positions that are -1 where a row has no partner, the validity lanes of
every right-side column), first device. The stage and the reader are
``join_emit_ms``'s; the cells differ, and that metric's list of cells is
not this PR's to append to."""
from chipbench.layer_metrics.join_emit_ms import read  # noqa: F401
