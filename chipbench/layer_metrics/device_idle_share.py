"""``device_idle_share``: 1 minus the union of the device's operation
intervals over the traced window, averaged over the chips used."""


def read(obs: dict):
    trace = obs["trace"]
    if not trace or not trace["devices"] or trace["window_s"] <= 0:
        return None
    busy = [d["busy_s"] for d in trace["devices"].values()]
    return 100.0 * (1.0 - sum(busy) / len(busy) / trace["window_s"])
