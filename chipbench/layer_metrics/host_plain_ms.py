"""``host_plain_ms``: the Python between the programs: a public call's
duration plus its tail (the deferred count fetch that the caller's
``row_count`` triggers), less the waits in fetches and the program calls
(``plain_ns`` of the always-on record), mean over the window's queries.
``None`` where the program keeps no such records."""
from chipbench.layer_metrics.host_exposed_ms import mean_ms


def read(obs: dict):
    return mean_ms(obs, "plain_ns")
