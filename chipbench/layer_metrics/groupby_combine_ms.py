"""``groupby_combine_ms``: device self time a query of the operations whose
stage is ``groupby.combine`` (the shards' partial slot tables of a dense
group-by combined over the mesh axis: a ``psum`` of the counts, an
``all_gather`` of the float sums and their fold in shard order), first
device. ``None`` where the stage did not run (one chip; a checkout from
before the stage). See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.combine")
