"""``join_replicate_ms``: device self time a query of the operations whose
stage is ``join.replicate`` (the replicate route of a distributed join:
the small side's lanes and counts gathered to every chip and front-packed
by block writes, ``parallel/shuffle.replicate_cols``), first device.
``None`` where the stage did not run (one chip; the shuffle route; a
checkout from before the route). See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "join.replicate")
