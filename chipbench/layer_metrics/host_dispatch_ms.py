"""``host_dispatch_ms``: host time inside program calls a query:
``dispatch_ns`` of the public call's always-on record (every call through
``engine.get_kernel`` reads the clock before and after the jitted call),
mean over the window's queries, the tail's included. What the host pays to
enqueue the query's programs; a compile inside the window would show here.
``None`` where the program keeps no such records."""
from chipbench.layer_metrics.host_exposed_ms import mean_ms


def read(obs: dict):
    return mean_ms(obs, "dispatch_ns")
