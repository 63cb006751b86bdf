"""``groupby_merge_ms``: device self time a query of the operations whose
stage is ``groupby.merge`` (the combine of a group-by across chips: the
partial rows a shard received reduced to its own groups and the aggregates
finished from them, one program), first device. ``None`` where the stage
did not run (one chip; a group-by combined in place; a checkout from
before the stage). See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.merge")
