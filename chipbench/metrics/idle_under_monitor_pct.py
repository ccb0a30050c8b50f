"""Share of the window's device idle time during which the monitor
thread was draining (a ``monitor.drain`` span open): the time the
monitor may hold the interpreter lock while the chip waits."""
from chipbench import program_spans


def read(run):
    if not program_spans.in_window(run, "serve.dispatch"):
        return None
    idle = program_spans.idle_ns(run.trace)
    under = program_spans.idle_under(
        run.trace, program_spans.in_window(run, "monitor.drain"))
    if not idle or under is None:
        return None
    return 100.0 * under / idle
