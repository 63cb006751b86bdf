"""``shuffle_pack_ms``: device self time a query of the operations whose
stage is ``shuffle.pack`` (partition ids, send slots and the scatter into
the send buffers), first device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "shuffle.pack")
