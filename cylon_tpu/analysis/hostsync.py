"""Host-sync monitoring: who fetched device data, and from where.

Every eager dispatch path funnels its device->host transfers through
``cylon_tpu.table._fetch`` (the multi-process-safe fetch helper). The
monitor swaps in a recording wrapper and attributes each fetch to the
nearest enclosing ``cylon_tpu`` (or caller-supplied) stack frame, so a
contract can whitelist exactly the fetches a path is designed to make —
for the chunked shuffle, the count-phase fetch and the ONE deferred
round-count fetch, both in ``_shuffle_many`` — and flag anything else,
in particular a sync that sneaks into the round dispatch loop (its count
would also scale with K, which the contracts'
K-independence check catches even if the site name matches).

The monitored runs happen in :mod:`.plans` on the dryrun mesh; the
``mid-loop sync`` known-bad fixture in ``tests/test_analysis.py``
demonstrates a violation.
"""
from __future__ import annotations

import contextlib
import sys
from dataclasses import dataclass
from typing import Iterator, List, Optional


@dataclass(frozen=True)
class SyncEvent:
    site: str   # function name of the nearest attributable frame
    file: str
    line: int


def _attribute(skip_modules=("hostsync",)) -> SyncEvent:
    f = sys._getframe(2)
    chosen = None
    while f is not None:
        name = f.f_code.co_name
        fn = f.f_code.co_filename
        if not any(m in fn for m in skip_modules):
            chosen = (name, fn, f.f_lineno)
            break
        f = f.f_back
    if chosen is None:  # pragma: no cover - unattributable
        chosen = ("<unknown>", "<unknown>", 0)
    return SyncEvent(*chosen)


@contextlib.contextmanager
def sync_monitor() -> Iterator[List[SyncEvent]]:
    """Record every ``table._fetch`` call (site-attributed) while active."""
    from .. import table as _table

    events: List[SyncEvent] = []
    real = _table._fetch

    def spy(arr, site):
        events.append(_attribute())
        return real(arr, site)

    _table._fetch = spy
    try:
        yield events
    finally:
        _table._fetch = real


def sites(events: List[SyncEvent]) -> List[str]:
    return [e.site for e in events]
