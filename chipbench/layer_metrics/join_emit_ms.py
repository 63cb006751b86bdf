"""``join_emit_ms``: device self time a query of the operations whose
stage is ``join.emit`` (the repeat and the payload gathers that write the
joined rows), first device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "join.emit")
