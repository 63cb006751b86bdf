"""``shuffle_compact_ms``: device self time a query of the operations
whose stage is ``shuffle.compact`` (the front-pack of the received rows
and their unpacking), first device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "shuffle.compact")
