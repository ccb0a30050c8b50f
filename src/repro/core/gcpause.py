"""The database build with the cyclic garbage collector paused.

Flushing, writing and aggregating profiles build large, long-lived
structures (trees, columns, index maps).  The collector's passes while
they grow find next to no garbage, yet each young pass rescans what the
build has made so far, and a full pass scans the whole heap: everything
import, tracing and compilation left there.  Whether a full pass falls
inside a build depends on what the process allocated before it, so a
build's time swung by the length of one.  Paused, a build scans nothing
while it runs; what it made is looked at once, by the first young pass
after it.
"""
from __future__ import annotations

import contextlib
import gc


@contextlib.contextmanager
def collector_paused():
    """Pause the cyclic collector while open (also as a decorator), and
    leave it as it was found: a pause nested in another stays paused."""
    was = gc.isenabled()
    gc.disable()
    try:
        yield
    finally:
        if was:
            gc.enable()
