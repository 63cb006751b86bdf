"""``groupby_segment_ms``: device self time a query of the operations whose
stage is ``groupby.segment_sum`` (the per-element scatter-adds, -mins and
-maxes of the sort-and-segment group-by), first device. A query on the
dense path runs none. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "groupby.segment_sum")
