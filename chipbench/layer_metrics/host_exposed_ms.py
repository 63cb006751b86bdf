"""``host_exposed_ms``: the time a query left the device with nothing to
do, as the program itself reckons it: ``exposed_ns`` of the always-on
record that every public call leaves (``cylon_tpu.obs.last_ops``; from the
record's start to its first program call's return, and from every fetch's
return to the next program call's return, the tail's deferred count fetch
included), mean over the window's queries. Every cell's query is one public
call, and nothing of the program runs between the window's end and the
readers, so the last ``obs["queries"]`` records are the window's. It is the
program's own estimate of what ``device_idle_share`` measures from the
other side. ``None`` where the program keeps no such records (a commit
from before them)."""


def window_records(obs: dict):
    """The window's records, oldest first, or ``None``."""
    try:
        from cylon_tpu.obs import last_ops
    except ImportError:
        return None
    queries = int(obs.get("queries") or 0)
    records = last_ops(queries) if queries else []
    return records if queries and len(records) == queries else None


def mean_ms(obs: dict, field: str):
    """Mean over the window's records of a field in nanoseconds, in ms."""
    records = window_records(obs)
    if records is None:
        return None
    return sum(r[field] for r in records) / len(records) / 1e6


def read(obs: dict):
    return mean_ms(obs, "exposed_ns")
