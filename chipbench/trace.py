"""Reduction of a ``jax.profiler`` trace to the numbers the per-layer
metrics read: device busy and idle time, device time per program (by its
jit name), the idle gaps between programs of one name, and what the host
was doing in each idle gap, by the benchmark's own ``TraceAnnotation``
spans (named ``chipbench:<what>``).

The trace is read with ``jax.profiler.ProfileData`` and nothing else.
Device planes are named ``/device:TPU:<n>``; on each, the ``XLA Ops``
line holds one event per operation run and the ``XLA Modules`` line one
event per program execution.  Host planes hold the spans.
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

SPAN_PREFIX = "chipbench:"
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
ENQUEUE = "DoEnqueueProgram"         # host event, carries the run_id
WINDOW = SPAN_PREFIX + "window"      # the benchmark's span of the window
NO_SPAN = "in the entry's own loop (no benchmark span)"


def program_name(event_name: str) -> str:
    """``jit_decode_step(42)`` -> ``decode_step``."""
    name = event_name.split("(", 1)[0].strip()
    return name[4:] if name.startswith("jit_") else name


def union_intervals(starts: np.ndarray, ends: np.ndarray
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Merge [start, end) intervals into sorted, disjoint ones."""
    if not len(starts):
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], ends[order]
    run_end = np.maximum.accumulate(e)
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > run_end[:-1]
    idx = np.flatnonzero(new)
    last = np.append(idx[1:] - 1, len(s) - 1)
    return s[idx], run_end[last]


def covered(us: np.ndarray, ue: np.ndarray, t0: float, t1: float) -> float:
    """Length of [t0, t1) covered by the disjoint sorted intervals."""
    if t1 <= t0 or not len(us):
        return 0.0
    return float(np.clip(np.minimum(ue, t1) - np.maximum(us, t0), 0,
                         None).sum())


@dataclasses.dataclass
class DeviceTrace:
    """One device's operations and program executions (ns)."""
    op_names: List[str]
    op_start: np.ndarray
    op_end: np.ndarray
    prog_names: List[str]
    prog_start: np.ndarray
    prog_end: np.ndarray


@dataclasses.dataclass
class Reduction:
    """The traced window, reduced.  Times in seconds unless named _ns."""
    t0_ns: float
    t1_ns: float
    devices: List[DeviceTrace]
    spans: List[Tuple[str, float, float]]     # (name, start_ns, end_ns)
    _span_arrays: Optional[tuple] = dataclasses.field(default=None,
                                                      repr=False)

    @property
    def window_s(self) -> float:
        return (self.t1_ns - self.t0_ns) / 1e9

    def _busy(self, dev: DeviceTrace):
        return union_intervals(dev.op_start, dev.op_end)

    @property
    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the chips."""
        if not self.devices:
            return 0.0
        return float(np.mean([covered(*self._busy(d), self.t0_ns,
                                      self.t1_ns) for d in self.devices])
                     ) / 1e9

    def idle_pct(self) -> Optional[float]:
        if not self.devices or self.window_s <= 0:
            return None
        return 100.0 * (1.0 - self.busy_s / self.window_s)

    def program_times(self, name: str) -> np.ndarray:
        """Device time (s) of each execution of program ``name`` inside
        the window, on the first chip."""
        if not self.devices:
            return np.zeros(0)
        d = self.devices[0]
        sel = np.array([program_name(n) == name for n in d.prog_names],
                       bool)
        if not sel.any():
            return np.zeros(0)
        s, e = d.prog_start[sel], d.prog_end[sel]
        inside = (s >= self.t0_ns) & (e <= self.t1_ns)
        return (e[inside] - s[inside]) / 1e9

    def gaps_between(self, name: str, unless: Sequence[str] = ()
                     ) -> np.ndarray:
        """Device-idle seconds between the end of each execution of
        ``name`` and the start of the next one, on the first chip; pairs
        with a program named in ``unless`` between them are left out."""
        if not self.devices:
            return np.zeros(0)
        d = self.devices[0]
        names = np.array([program_name(n) for n in d.prog_names], object)
        inside = (d.prog_start >= self.t0_ns) & (d.prog_end <= self.t1_ns)
        order = np.argsort(d.prog_start[inside], kind="stable")
        names = names[inside][order]
        s, e = d.prog_start[inside][order], d.prog_end[inside][order]
        us, ue = self._busy(d)
        barrier = np.cumsum(np.isin(names, list(unless)))
        out = []
        idx = np.flatnonzero(names == name)
        for a, b in zip(idx[:-1], idx[1:]):
            if barrier[b] != barrier[a]:
                continue
            out.append((s[b] - e[a]) - covered(us, ue, e[a], s[b]))
        return np.asarray(out, np.float64) / 1e9

    def idle_gaps(self) -> List[Tuple[float, float]]:
        """Idle intervals (ns) of the first chip inside the window."""
        if not self.devices:
            return []
        us, ue = self._busy(self.devices[0])
        edges_s = np.concatenate(([self.t0_ns], ue))
        edges_e = np.concatenate((us, [self.t1_ns]))
        edges_s = np.clip(edges_s, self.t0_ns, self.t1_ns)
        edges_e = np.clip(edges_e, self.t0_ns, self.t1_ns)
        keep = edges_e > edges_s
        return list(zip(edges_s[keep].tolist(), edges_e[keep].tolist()))

    def labels(self, a: np.ndarray, b: np.ndarray) -> List[str]:
        """For each interval [a, b): the benchmark span, other than the
        window's own, that holds its midpoint (the benchmark's spans on
        one thread follow one another), else ``NO_SPAN``."""
        if self._span_arrays is None:
            inner = sorted((s, e, n) for n, s, e in self.spans
                           if n != WINDOW)
            self._span_arrays = (
                [n for _, _, n in inner],
                np.array([s for s, _, _ in inner], np.float64),
                np.array([e for _, e, _ in inner], np.float64))
        names, s, e = self._span_arrays
        mid = (np.asarray(a) + np.asarray(b)) / 2
        i = np.searchsorted(s, mid, side="right") - 1
        ok = (i >= 0) & (mid < e[np.maximum(i, 0)]) if len(s) else \
            np.zeros(len(mid), bool)
        return [names[j] if hit else NO_SPAN for j, hit in zip(i, ok)]

    def breakdown(self, top: int = 10) -> dict:
        """The device operations that took most time (self time: an
        operation's time less that of the operations nested in it, such
        as a loop's body), and the idle time grouped by what the host
        was doing, each at most ``top`` rows."""
        ops: Dict[str, float] = {}
        if self.devices:
            d = self.devices[0]
            inside = (d.op_start >= self.t0_ns) & (d.op_end <= self.t1_ns)
            idx = np.flatnonzero(inside)
            own = self_times(d.op_start[idx], d.op_end[idx])
            for i, t in zip(idx, own):
                n = op_name(d.op_names[i])
                ops[n] = ops.get(n, 0.0) + t / 1e9
        gaps: Dict[str, float] = {}
        idle = self.idle_gaps()
        if idle:
            a, b = np.array(idle).T
            for lab, dt in zip(self.labels(a, b), b - a):
                gaps[lab] = gaps.get(lab, 0.0) + dt / 1e9
        rank = lambda d: sorted(d.items(), key=lambda kv: -kv[1])[:top]
        return {"device_ops": [[k, v] for k, v in rank(ops)],
                "idle_gaps": [[k, v] for k, v in rank(gaps)]}


def op_name(hlo: str) -> str:
    """``%fusion.3 = bf16[...] fusion(...)`` -> ``fusion.3``."""
    return hlo.split(" = ", 1)[0].lstrip("%").strip()


def self_times(starts: np.ndarray, ends: np.ndarray) -> np.ndarray:
    """Each interval's length less that of the intervals nested in it
    (intervals on one line either nest or follow one another)."""
    order = np.argsort(starts, kind="stable")
    own = (ends - starts).astype(np.float64)
    stack: List[int] = []
    for i in order:
        while stack and ends[stack[-1]] <= starts[i]:
            stack.pop()
        if stack and ends[i] <= ends[stack[-1]]:
            own[stack[-1]] -= ends[i] - starts[i]
        stack.append(i)
    return own


def _events(line):
    return [(ev.name, float(ev.start_ns),
             float(ev.start_ns) + float(ev.duration_ns)) for ev in line.events]


def _run_ids(line, name: Optional[str] = None
             ) -> Dict[str, Tuple[float, float]]:
    """run_id -> (start, end) of the line's events (named ``name``, if
    given) that carry one."""
    out = {}
    for ev in line.events:
        if name is not None and ev.name != name:
            continue
        for k, v in ev.stats:
            if k == "run_id":
                out[str(v)] = (float(ev.start_ns),
                               float(ev.start_ns) + float(ev.duration_ns))
                break
    return out


def device_shift(enqueued: Dict[str, Tuple[float, float]],
                 executed: Dict[str, Tuple[float, float]]) -> float:
    """Nanoseconds to add to device times to put them on the host's
    clock.  The device clock is converted with an offset of its own; a
    program cannot start before the host has enqueued it, so the shift
    is the least that puts every execution after its enqueue (paired by
    ``run_id``)."""
    common = set(enqueued) & set(executed)
    if not common:
        return 0.0
    return max(enqueued[r][1] - executed[r][0] for r in common)


def start(trace_dir: str) -> None:
    """Start a ``jax.profiler`` trace with the Python function tracer
    off: it records every Python call, which slows the host many times
    over and would show as device idle time."""
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)


def find_trace_file(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def reduce_trace(path: str, window: Optional[Tuple[str, str]] = None,
                 chips: Optional[int] = None) -> Reduction:
    """Read ``path`` (an ``.xplane.pb``).  The window runs from the start
    of the first span named ``window[0]`` to the end of the last span
    named ``window[1]`` (default: the span ``chipbench:window``); without
    such spans, from the first to the last device operation."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    devices: Dict[int, DeviceTrace] = {}
    spans: List[Tuple[str, float, float]] = []
    enqueued: Dict[str, Tuple[float, float]] = {}
    executed: Dict[str, Tuple[float, float]] = {}
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        if m:
            ops, progs = [], []
            for line in plane.lines:
                if line.name == OPS_LINE:
                    ops = _events(line)
                elif line.name == MODULES_LINE:
                    progs = _events(line)
                    if int(m.group(1)) == 0:
                        executed = _run_ids(line)
            arr = lambda evs, i: np.array([e[i] for e in evs], np.float64)
            devices[int(m.group(1))] = DeviceTrace(
                [e[0] for e in ops], arr(ops, 1), arr(ops, 2),
                [e[0] for e in progs], arr(progs, 1), arr(progs, 2))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                spans += [e for e in _events(line)
                          if e[0].startswith(SPAN_PREFIX)]
                enqueued.update(_run_ids(line, ENQUEUE))
    shift = device_shift(enqueued, executed)
    for d in devices.values():
        for a in (d.op_start, d.op_end, d.prog_start, d.prog_end):
            a += shift
    devs = [devices[k] for k in sorted(devices)]
    if chips is not None:
        devs = devs[:chips]
    first, last = window or (WINDOW, WINDOW)
    starts = [s for n, s, _ in spans if n == first]
    ends = [e for n, _, e in spans if n == last]
    if starts and ends:
        t0, t1 = min(starts), max(ends)
    else:
        all_s = [d.op_start for d in devs if len(d.op_start)]
        all_e = [d.op_end for d in devs if len(d.op_end)]
        t0 = min(float(a.min()) for a in all_s) if all_s else 0.0
        t1 = max(float(a.max()) for a in all_e) if all_e else 0.0
    return Reduction(t0, t1, devs, spans)


def reduce_dir(trace_dir: str, chips: Optional[int] = None) -> Reduction:
    return reduce_trace(find_trace_file(trace_dir), chips=chips)
