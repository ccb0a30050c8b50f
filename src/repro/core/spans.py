"""Program spans on the clock of a ``jax.profiler`` trace.

``span(name, **args)`` is a ``jax.profiler.TraceAnnotation`` named
``repro:<name>``: it lands in the trace's ``.xplane.pb`` on the host
timeline, beside the runtime's own enqueue events, so device idle time
can be put down to the program span the host was in.  Keyword args come
back as the event's stats (``ProfileData``).

Spans record exactly while a ``jax.profiler`` trace runs; there is no
other switch.  With no trace active, ``span()`` returns one shared no-op
context and constructs nothing.  The module never imports jax itself: a
trace can only be running once ``jax.profiler`` has been imported, so
the measurement core stays importable (and cheap to import) without it.
"""
from __future__ import annotations

import contextlib
import sys

PREFIX = "repro:"

OFF = contextlib.nullcontext()      # the shared no-op; enters as None
_annotation = None                  # jax.profiler.TraceAnnotation, once seen


def span(name: str, **args):
    """A ``TraceAnnotation`` named ``repro:<name>`` carrying ``args``
    while a trace is active, else ``OFF``.  Entering a live span returns
    it, so args known only at the end go in through ``set_metadata``."""
    ta = _annotation or _find()
    if ta is None or not ta.is_enabled():
        return OFF
    return ta(PREFIX + name, **args)


def _find():
    global _annotation
    mod = sys.modules.get("jax.profiler")
    if mod is not None:
        _annotation = mod.TraceAnnotation
    return _annotation
