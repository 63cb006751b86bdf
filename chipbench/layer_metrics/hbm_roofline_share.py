"""``hbm_roofline_share``: the least time the chips' HBM could take for a
query (the bytes it cannot avoid moving, from the query's own
``least_bytes``, over the peak of ``peaks.json`` times the chips) as a share
of the device busy time a query took in the trace. Bound: HBM bytes; the
queries do no arithmetic to speak of."""


def read(obs: dict):
    trace = obs["trace"]
    if not trace or not trace["devices"] or not obs["queries"]:
        return None
    busy = [d["busy_s"] for d in trace["devices"].values()]
    busy_per_query = sum(busy) / len(busy) / obs["queries"]
    if busy_per_query <= 0:
        return None
    least_s = obs["least_bytes"] / (
        obs["peaks"]["hbm_bytes_per_s"] * len(busy)
    )
    return 100.0 * least_s / busy_per_query
