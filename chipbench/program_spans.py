#!/usr/bin/env python3
"""The program's own spans in a traced run, and the device idle time
they overlap.

The program marks its serving step with ``jax.profiler`` annotations
named ``repro:<name>`` (``repro.core.spans``), whose keyword args come
back as event stats.  They sit on the host timeline of the same
``.xplane.pb`` as the runtime's enqueue events, which ``trace.py`` pairs
with device programs to move device time onto the host clock; so the
spans are read here on the host clock as recorded, and compared with
the reduction's idle gaps directly.  ``trace.py`` keeps the benchmark's
own spans; this module reads the trace file again for the program's.

A traced run's trace lies where ``run.py`` puts it,
``<root>/.chipbench/<cell>/trace``.  A program without these spans (one
older than them) gives an empty list, and every reader then returns
None.

    python3 chipbench/program_spans.py <trace dir>

prints a report of one traced run as JSON: the device idle time split by
the serving thread's innermost span, the share under the monitor's
drains, the governor's rung residency, and the check that each decode
``serve.enqueue`` span holds the runtime's enqueue of its program and
that the program starts on the device after the span does.
"""
from __future__ import annotations

import functools
import json
import os
import sys
from typing import Callable, Dict, List, NamedTuple, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
if __name__ == "__main__":
    sys.path.insert(0, os.path.dirname(HERE))

from chipbench import trace  # noqa: E402

PREFIX = "repro:"
SERVING_THREAD = "serve.dispatch"     # the span that marks that thread
NO_SPAN = "(no repro span)"


class Span(NamedTuple):
    name: str                # without the prefix
    start_ns: float
    end_ns: float
    args: dict
    line: tuple              # (plane, line index, line name): its thread


def read(path: str) -> List[Span]:
    """Every ``repro:*`` host event of the ``.xplane.pb`` at ``path``,
    in order of start."""
    st = os.stat(path)
    return _read(path, st.st_mtime_ns, st.st_size)


@functools.lru_cache(maxsize=2)
def _read(path: str, _mtime: int, _size: int) -> List[Span]:
    from jax.profiler import ProfileData
    out = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith("/host:"):
            continue
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(PREFIX):
                    s = float(ev.start_ns)
                    out.append(Span(ev.name[len(PREFIX):], s,
                                    s + float(ev.duration_ns),
                                    {k: v for k, v in ev.stats},
                                    (plane.name, li, line.name)))
    out.sort(key=lambda s: s.start_ns)
    return out


def trace_dir(run) -> str:
    return os.path.join(os.path.dirname(run.cell.base), ".chipbench",
                        run.cell.name, "trace")


def of_run(run) -> List[Span]:
    """The program spans of a traced run; empty for an untraced one."""
    if run.trace is None:
        return []
    try:
        return read(trace.find_trace_file(trace_dir(run)))
    except FileNotFoundError:
        return []


def in_window(run, name: str, **args) -> List[Span]:
    """Spans named ``name`` (with these args) that lie inside the
    measured window."""
    if run.trace is None:
        return []
    t0, t1 = run.trace.t0_ns, run.trace.t1_ns
    return [s for s in of_run(run) if s.name == name
            and t0 <= s.start_ns and s.end_ns <= t1
            and all(s.args.get(k) == v for k, v in args.items())]


def mean_us(spans: List[Span]) -> Optional[float]:
    if not spans:
        return None
    return float(np.mean([s.end_ns - s.start_ns for s in spans])) / 1e3


def tick_delta(run, *keys: str) -> Optional[Dict[str, float]]:
    """How much each counter of the ``serving.tick`` args grew from the
    first tick in the window to the last."""
    ticks = [t for t in in_window(run, "serving.tick")
             if all(k in t.args for k in keys)]
    if len(ticks) < 2:
        return None
    return {k: float(ticks[-1].args[k] - ticks[0].args[k]) for k in keys}


# ---------------------------------------------------------------------------
# device idle time under spans
# ---------------------------------------------------------------------------
def _idle_before(red) -> Optional[Callable]:
    """F(t): device idle ns of the window before t (first chip)."""
    gaps = red.idle_gaps()
    if not gaps:
        return None
    gs, ge = (np.asarray(a, np.float64) for a in zip(*gaps))
    cum = np.concatenate(([0.0], np.cumsum(ge - gs)))

    def before(t):
        t = np.asarray(t, np.float64)
        i = np.searchsorted(gs, t, side="right") - 1
        j = np.maximum(i, 0)
        part = np.clip(t - gs[j], 0.0, ge[j] - gs[j])
        return np.where(i >= 0, cum[j] + part, 0.0)
    return before


def idle_ns(red) -> float:
    """All device idle ns in the window (first chip)."""
    return float(sum(e - s for s, e in red.idle_gaps()))


def idle_under(red, spans: List[Span]) -> Optional[float]:
    """Device idle ns in the window during which at least one of
    ``spans`` is open (the union of the spans, so overlaps count once);
    None when the trace has no device."""
    before = _idle_before(red)
    if before is None:
        return None
    if not spans:
        return 0.0
    us, ue = trace.union_intervals(
        np.array([s.start_ns for s in spans], np.float64),
        np.array([s.end_ns for s in spans], np.float64))
    return float((before(ue) - before(us)).sum())


def innermost_segments(spans: List[Span]):
    """For spans of one thread (they nest or follow one another): the
    stretches of time and the innermost span open over each, as
    (starts, ends, names)."""
    spans = [s for s in spans if s.end_ns > s.start_ns]
    # at one instant, ends go first; of spans that start together, the
    # outer (longer) one goes first
    events = sorted([(s.start_ns, 1, -s.end_ns, i)
                     for i, s in enumerate(spans)]
                    + [(s.end_ns, 0, 0.0, i) for i, s in enumerate(spans)])
    a, b, names = [], [], []
    stack: List[int] = []
    prev = None
    for t, is_start, _, i in events:
        if stack and prev is not None and t > prev:
            a.append(prev)
            b.append(t)
            names.append(spans[stack[-1]].name)
        prev = t
        if is_start:
            stack.append(i)
        else:
            stack.remove(i)
    return np.array(a, np.float64), np.array(b, np.float64), names


def idle_by_innermost(red, spans: List[Span]) -> Dict[str, float]:
    """Device idle seconds of the window split by the serving thread's
    innermost program span; the rest under ``NO_SPAN``."""
    before = _idle_before(red)
    if before is None:
        return {}
    lines = {s.line for s in spans if s.name == SERVING_THREAD}
    mine = [s for s in spans if s.line in lines]
    a, b, names = innermost_segments(mine)
    out: Dict[str, float] = {}
    for n, dt in zip(names, before(b) - before(a)):
        out[n] = out.get(n, 0.0) + float(dt) / 1e9
    out[NO_SPAN] = idle_ns(red) / 1e9 - sum(out.values())
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


# ---------------------------------------------------------------------------
# the report
# ---------------------------------------------------------------------------
def level_residency(ticks: List[Span]) -> Dict[str, float]:
    """Share of the time between the first and the last tick that the
    governor spent on each rung (the ``level`` a tick left it on holds
    until the next tick)."""
    ticks = [t for t in ticks if "level" in t.args]
    if len(ticks) < 2:
        return {}
    out: Dict[str, float] = {}
    total = ticks[-1].end_ns - ticks[0].end_ns
    for t, nxt in zip(ticks[:-1], ticks[1:]):
        k = str(t.args["level"])
        out[k] = out.get(k, 0.0) + (nxt.end_ns - t.end_ns) / total
    return out


def _quartiles_us(x) -> List[float]:
    return [float(v) / 1e3 for v in np.percentile(x, [25, 50, 75])] \
        if len(x) else []


def clock_check(path: str, red, spans: List[Span]) -> dict:
    """Each decode step of the window on the shared clock: the
    runtime's enqueue of its ``decode_step`` program (the first one
    enqueued at or after its ``serve.enqueue`` span starts) and that
    program's device run, moved as ``trace.py`` moves device times.

    Counts the steps whose enqueue lies inside the ``serve.enqueue``
    span and inside the step (before its ``serve.sync`` ends), and whose
    program starts on the device after the span starts; gives quartiles
    (us) of the enqueue's lag after the span ends, of the program's
    start after it, and of the host's wake after the program ends; and
    splits the device idle time inside ``serve.sync`` into before the
    program starts and after it ends."""
    from jax.profiler import ProfileData
    enq: Dict[str, tuple] = {}
    progs: Dict[str, tuple] = {}
    for plane in ProfileData.from_file(path).planes:
        m = trace.DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m and int(m.group(1)) == 0 and line.name == trace.MODULES_LINE:
                for ev in line.events:
                    for k, v in ev.stats:
                        if k == "run_id":
                            s0 = float(ev.start_ns)
                            progs[str(v)] = (trace.program_name(ev.name),
                                             s0, s0 + float(ev.duration_ns))
            elif plane.name.startswith("/host:"):
                for ev in line.events:
                    if ev.name != trace.ENQUEUE:
                        continue
                    for k, v in ev.stats:
                        if k == "run_id":
                            s0 = float(ev.start_ns)
                            enq[str(v)] = (s0, s0 + float(ev.duration_ns),
                                           line.name)
    shift = trace.device_shift(enq, {r: p[1:] for r, p in progs.items()})
    steps = sorted(enq[r] + (progs[r][1] + shift, progs[r][2] + shift)
                   for r in set(enq) & set(progs)
                   if progs[r][0] == "decode_step")
    enq_starts = np.array([x[0] for x in steps], np.float64)

    def window_spans(name):
        return [x for x in spans if x.name == name
                and x.args.get("phase") == "decode"
                and red.t0_ns <= x.start_ns and x.end_ns <= red.t1_ns]
    syncs = window_spans("serve.sync")
    sync_starts = np.array([x.start_ns for x in syncs], np.float64)
    before = _idle_before(red)
    held = in_step = after = 0
    lag, launch, wake = [], [], []
    idle_pre = idle_post = 0.0
    threads: Dict[str, int] = {}
    enqueues = window_spans("serve.enqueue")
    for sp in enqueues:
        i = int(np.searchsorted(enq_starts, sp.start_ns))
        j = int(np.searchsorted(sync_starts, sp.end_ns))
        if i == len(steps) or j == len(syncs):
            continue
        e0, e1, line, d0, d1 = steps[i]
        sync = syncs[j]
        held += e1 <= sp.end_ns
        in_step += e1 <= sync.end_ns
        after += d0 >= sp.start_ns
        threads[line] = threads.get(line, 0) + 1
        lag.append(e0 - sp.end_ns)
        launch.append(d0 - sp.end_ns)
        wake.append(sync.end_ns - d1)
        if before is not None:
            idle_pre += float(before(max(d0, sync.start_ns))
                              - before(sync.start_ns))
            idle_post += float(before(sync.end_ns)
                               - before(min(max(d1, sync.start_ns),
                                            sync.end_ns)))
    return {"decode_enqueue_spans": len(enqueues),
            "enqueue_inside_span": held,
            "enqueue_inside_step": in_step,
            "program_starts_after_span_start": after,
            "enqueue_threads": threads,
            "enqueue_lag_after_span_us_q": _quartiles_us(lag),
            "program_start_after_span_us_q": _quartiles_us(launch),
            "host_wake_after_program_us_q": _quartiles_us(wake),
            "sync_idle_before_program_s": idle_pre / 1e9,
            "sync_idle_after_program_s": idle_post / 1e9,
            "device_shift_ns": shift}


def report(trace_dir_: str) -> dict:
    path = trace.find_trace_file(trace_dir_)
    red = trace.reduce_trace(path)
    spans = read(path)
    inside = [s for s in spans
              if s.end_ns > red.t0_ns and s.start_ns < red.t1_ns]
    serving = {s.line for s in inside if s.name == SERVING_THREAD}
    idle = idle_ns(red)

    def pct(spans):
        under = idle_under(red, spans)
        return 100.0 * under / idle if idle and under is not None else None
    ticks = [s for s in inside if s.name == "serving.tick"]
    return {
        "window_s": red.window_s, "idle_s": idle / 1e9,
        "idle_in_some_repro_span_pct": pct(inside),
        "idle_in_serving_thread_span_pct":
            pct([s for s in inside if s.line in serving]),
        "idle_under_monitor_drain_pct":
            pct([s for s in inside if s.name == "monitor.drain"]),
        "idle_by_innermost_span_s": idle_by_innermost(red, inside),
        "spans": {n: sum(1 for s in inside if s.name == n)
                  for n in sorted({s.name for s in inside})},
        "level_residency": level_residency(ticks),
        "clock": clock_check(path, red, spans),
    }


if __name__ == "__main__":
    print(json.dumps(report(sys.argv[1]), indent=1))
