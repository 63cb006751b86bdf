"""``stage_unattributed_share``: share of the first device's busy self
time on operations the program's stage table gives no stage, gives two
different stages under one short name, or lists under a stale module. The
per-stage metrics are only as good as this is small. See
``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    found = stage_times.split(obs)
    return None if found is None else found["unattributed_share"]
