"""The profiler's own time on the dispatch path per dispatch (its
``tool_ns`` over its ``dispatches``, ``Profiler.overhead_counters``),
grown from the first to the last ``serving.tick`` in the window: the
ticks carry the counters as args."""
from chipbench import program_spans


def read(run):
    d = program_spans.tick_delta(run, "tool_ns", "dispatches")
    if not d or d["dispatches"] <= 0:
        return None
    return d["tool_ns"] / d["dispatches"] / 1e3
