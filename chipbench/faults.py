"""Faults planted under the timed path, to show that ``correct`` catches
each one a serving cell can have.  Each is a context manager that breaks
the program's own step or profiler while it is open.

    python3 chipbench/run.py --workload <name> --seeds 1 2 3 \\
        --seconds <s> --fault stale-cache
"""
from __future__ import annotations

import contextlib

import jax.numpy as jnp


@contextlib.contextmanager
def _patched(obj, name, value):
    own = getattr(obj, name)
    setattr(obj, name, value)
    try:
        yield
    finally:
        setattr(obj, name, own)


def _broken_decode(change):
    """The program's decode steps, each passing its output through
    ``change(logits, cache_in, cache_out) -> (logits, cache)``."""
    from repro.launch import steps as steps_mod
    make = steps_mod.make_decode_step

    def broken(*a, **kw):
        step = make(*a, **kw)

        def decode_step(params, cache, pos, token=None, embed=None):
            logits, new = step(params, cache, pos, token=token, embed=embed)
            return change(logits, cache, new)
        return decode_step
    return _patched(steps_mod, "make_decode_step", broken)


def stale_cache():
    """A decode step that returns the KV cache it was given: the serving
    state is left unchanged."""
    return _broken_decode(lambda logits, old, new: (logits, old))


def token_altered():
    """Each decode step's logits shifted by one token: the served token
    is altered where it is produced."""
    return _broken_decode(
        lambda logits, old, new: (jnp.roll(logits, 1, axis=-1), new))


@contextlib.contextmanager
def dispatch_lost():
    """The profiler records nothing for the third dispatch."""
    from repro.core.profiler import Profiler
    own = Profiler.dispatch
    calls = []

    def lossy(self, *a, **kw):
        calls.append(a)
        if len(calls) == 3:
            return contextlib.nullcontext()
        return own(self, *a, **kw)
    with _patched(Profiler, "dispatch", lossy):
        yield


FAULTS = {"stale-cache": stale_cache, "token-altered": token_altered,
          "dispatch-lost": dispatch_lost}


def planted(name):
    """The fault called ``name``, or nothing planted for None."""
    return FAULTS[name]() if name else contextlib.nullcontext()
