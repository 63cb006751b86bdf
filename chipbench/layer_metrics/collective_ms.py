"""``collective_ms``: device time a query spends in all-to-all, all-gather,
all-reduce, reduce-scatter and collective-permute operations, from the
profiler trace, first device. A trace with no such operation (one chip)
gives nothing to read."""


def read(obs: dict):
    trace = obs["trace"]
    if not trace or not trace["devices"] or not obs["queries"]:
        return None
    first = trace["devices"][sorted(trace["devices"])[0]]
    if first["collective_s"] <= 0:
        return None
    return 1e3 * first["collective_s"] / obs["queries"]
