"""PC samples the profiler kept, as a share of those its full rate
would have drawn (kept + dropped by the governor's rung), grown from
the first to the last ``serving.tick`` in the window: a speed-up bought
by shedding fidelity shows here."""
from chipbench import program_spans


def read(run):
    d = program_spans.tick_delta(run, "samples_kept", "samples_dropped")
    if not d or d["samples_kept"] + d["samples_dropped"] <= 0:
        return None
    return 100.0 * d["samples_kept"] / (d["samples_kept"]
                                        + d["samples_dropped"])
