"""``groupby_dense_ms``: device self time a query of the operations whose
stage is ``groupby.dense_agg`` (the masked reductions of the dense
low-cardinality group-by and the emit of its few slots), first device. See
``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.dense_agg")
