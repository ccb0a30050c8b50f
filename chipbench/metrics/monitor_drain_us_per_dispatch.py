"""Monitor thread time draining the profiler's record rings (the
program's ``monitor.drain`` spans: deferred sample draw, attribution,
trace routing) in the window, per dispatch in the window."""
from chipbench import program_spans


def read(run):
    n = len(program_spans.in_window(run, "serve.dispatch"))
    if not n:
        return None
    drains = program_spans.in_window(run, "monitor.drain")
    return sum(s.end_ns - s.start_ns for s in drains) / n / 1e3
