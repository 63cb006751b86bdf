"""``host_syncs``: device-to-host count fetches a query, from the program's
always-on rollup counter ``host_sync`` (``obs/trace.bump``), as its
difference over the window divided by the window's queries."""


def read(obs: dict):
    if not obs["queries"] or "host_sync" not in obs["counters"]:
        return None
    return obs["counters"]["host_sync"] / obs["queries"]
