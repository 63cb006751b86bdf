"""``groupby_key_ids_ms``: device self time a query of the operations whose
stage is ``groupby.key_ids`` (the group-by's row ids: the factorize sort of
the sort-and-segment path, the arithmetic on the rebased keys of the dense
one), first device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.key_ids")
