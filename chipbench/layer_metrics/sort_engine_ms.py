"""``sort_engine_ms``: device self time a query of the operations with
``sort_engine`` anywhere on their ``op_name`` path (the radix passes and
the native sorts of ``ops/radix.py``, whichever stage called them), first
device. See ``chipbench/stage_times.py``."""
from chipbench import stage_times


def read(obs: dict):
    found = stage_times.split(obs)
    if found is None or found["sort_engine_ms"] <= 0:
        return None
    return found["sort_engine_ms"]
