"""``join_semi_ms``: device self time a query of the operations whose stage
is ``join.semi`` (the semi-reduction in front of a filtered join), first
device: in ``jit_join_semi`` the sort of the merged key ids with the
row's position as second key, the two blocked run scans and the
one-operand sort of the positions with a partner. NOT in it, though the
scope wraps them: the gathers of ``jit_join_reduce``, whose fusions share
their short names with the gathers of the counted join's emit (one name,
two stages: ``stage_times`` gives such a name no stage and counts it as
unattributed, PERF.md section 7), and the X64 splits in front of them,
which carry no ``op_name``. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "join.semi")
