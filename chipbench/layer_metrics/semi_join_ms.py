"""``semi_join_ms``: device self time a query of a semi or anti join as an
operator, first device: the stages ``join.semi`` (the keys-only work it
shares with the semi-reduction: the sort of the merged key ids with the
row's position as second key and the blocked run scan) and
``join.semi_mask`` (what the operator adds: the one-operand sort that
brings the verdicts back to row order). ``None`` where neither ran (a
commit from before the operator, another query). See
``chipbench/stage_times.py``."""
from chipbench import stage_times

STAGES = ("join.semi", "join.semi_mask")


def read(obs: dict):
    found = [stage_times.stage_ms(obs, s) for s in STAGES]
    if found[1] is None:
        return None  # no operator ran: ``join.semi`` alone is a reduction
    return sum(ms for ms in found if ms is not None)
