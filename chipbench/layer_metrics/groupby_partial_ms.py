"""``groupby_partial_ms``: device self time a query of the operations whose
stage is ``groupby.partial`` (the pre-combine of a group-by across chips:
a shard's own rows reduced to one partial row a group, the factorize sort,
the run reduction and the compaction sort nested inside it), first device.
``None`` where the stage did not run (one chip; a group-by combined in
place; a checkout from before the stage). See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.partial")
