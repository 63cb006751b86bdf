"""``topk_ms``: device self time a query of the operations whose stage is
``sort.topk`` (``Table.topk``: the sort of the key lanes and a row
position, and the gather of the rows kept), first device. See
``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    return stage_times.stage_ms(obs, "sort.topk")
