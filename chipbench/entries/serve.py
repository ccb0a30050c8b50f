"""Drives ``repro.launch.serve.serve`` under a started ``ServingProfiler``
(default governor) for one measured window, then builds the database.

``serve()`` takes no time limit, so the benchmark hands it, as its
``serving=`` hook, an object with the profiler and ``request()`` it
expects.  Each ``request()`` opens the real ``ServingProfiler`` window
and times it; the first one starts the measured window (``serve()`` has
compiled and warmed up both programs before it), and the first one
opened after ``--seconds`` have passed stops the job by raising
``WindowClosed``.  Before raising, it takes the generated tokens of the
finished requests (``outs``) from ``serve()``'s frame, and at every
prefill window the prompt it serves (``toks``): ``serve()`` returns
neither when it is stopped.

``serve()`` takes no weights either: while it runs, the program's
``init_params`` is swapped for the benchmark's own weights, made on the
device from the seed by the configuration file's recipe, after a check
that they have the very leaves, shapes and types the program's
initializer would have made.

The traffic file gives ``batch``, ``prompt_len``, ``gen_len`` and
``sample_requests`` (how many finished requests the reference checks).
"""
from __future__ import annotations

import contextlib
import gc
import os
import shutil
import sys
from typing import Dict, List, Optional

import numpy as np

from chipbench import harness
from chipbench.trace import SPAN_PREFIX

# serve() runs batches until stopped; the count only has to outlast any
# window (it is a range bound, nothing is allocated for it)
ENDLESS_BATCHES = 1_000_000


class WindowClosed(Exception):
    """Raised into serve() at the first window opened after the end."""


class Hook:
    """The ``serving=`` object handed to ``serve()``."""

    def __init__(self, sp, seconds: float, clock=harness.now,
                 trace_dir: Optional[str] = None):
        self.sp = sp
        self.profiler = sp.profiler
        self.seconds = seconds
        self.clock = clock
        self.trace_dir = trace_dir
        self.t0: Optional[float] = None
        self.t_stop: Optional[float] = None
        self.windows: List[tuple] = []      # (rid, phase, step, t_in, t_out)
        self.prompts: Dict[str, object] = {}
        self.opened: Dict[tuple, int] = {}
        self.finished: Optional[list] = None
        self._spans: list = []

    def request(self, rid, phase: str, *, tokens: int = 0):
        return _Window(self, str(rid), phase, tokens)

    # -- spans for the traced run ----------------------------------------
    def _span(self, name: str):
        if self.trace_dir is None:
            return None
        import jax
        a = jax.profiler.TraceAnnotation(SPAN_PREFIX + name)
        a.__enter__()
        return a

    @staticmethod
    def _end(span) -> None:
        if span is not None:
            span.__exit__(None, None, None)

    def start(self) -> None:
        if self.trace_dir is not None:
            from chipbench import trace
            trace.start(self.trace_dir)
            self._spans.append(self._span("window"))
        self.t0 = self.clock()

    def stop(self, serve_frame) -> None:
        self.t_stop = self.clock()
        self.finished = list(serve_frame.f_locals["outs"])
        while self._spans:
            self._end(self._spans.pop())


class _Window:
    def __init__(self, hook: Hook, rid: str, phase: str, tokens: int):
        self.hook, self.rid, self.phase, self.tokens = hook, rid, phase, tokens

    def __enter__(self):
        from repro.serving.window import PREFILL
        hook = self.hook
        if hook.t0 is None:
            hook.start()
        elif hook.clock() >= hook.t0 + hook.seconds:
            frame = sys._getframe(1)
            try:
                hook.stop(frame)
            finally:
                del frame
            raise WindowClosed()
        span = hook._span("open")
        if self.phase == PREFILL:
            hook.prompts[self.rid] = sys._getframe(1).f_locals["toks"]
        key = (self.rid, self.phase)
        self.step = hook.opened[key] = hook.opened.get(key, -1) + 1
        self.real = hook.sp.request(self.rid, self.phase,
                                    tokens=self.tokens)
        self.real.__enter__()
        self.t_in = hook.clock()
        hook._end(span)
        self.body = hook._span("dispatch+sync")
        return self

    def __exit__(self, *exc):
        hook = self.hook
        hook._end(self.body)
        span = hook._span("close (stats+tick)")
        t_body = hook.clock()
        self.real.__exit__(*exc)
        hook._end(span)
        if exc[0] is None:
            hook.windows.append((self.rid, self.phase, self.step,
                                 self.t_in, t_body))
        return False


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def seeded_weights(cell: harness.Cell, seed: int):
    """While open, the program's ``init_params`` returns the benchmark's
    weights for ``seed``, checked leaf by leaf against what the program's
    own initializer would make."""
    import jax
    from repro.models import transformer as T
    own = T.init_params

    def init_params(key, cfg):
        want = jax.eval_shape(lambda k: own(k, cfg), key)
        w = cell.reference.weights(cell.config, seed, untied_copy=True)
        got = jax.eval_shape(lambda: w)
        if jax.tree.structure(got) != jax.tree.structure(want) or any(
                (a.shape, a.dtype) != (b.shape, b.dtype) for a, b in
                zip(jax.tree.leaves(got), jax.tree.leaves(want))):
            raise ValueError(f"the weights of {cell.config_name!r} do not "
                             "match the program's parameters")
        return w
    T.init_params = init_params
    try:
        yield
    finally:
        T.init_params = own


def run(cell: harness.Cell, *, seed: int, seconds: float, trace: bool,
        out_dir: str, t_start: float, allow_cpu: bool = False
        ) -> harness.Run:
    import jax
    import jax.numpy as jnp
    from repro.core.aggregate import aggregate
    from repro.launch.serve import serve
    from repro.serving import ServingProfiler

    tr = cell.traffic
    cfg = harness.program_config(cell.config)
    device = harness.device_info(cell.chips, allow_cpu=allow_cpu)
    pseed = harness.program_seed(seed)
    B, P, G = tr["batch"], tr["prompt_len"], tr["gen_len"]

    shutil.rmtree(out_dir, ignore_errors=True)
    os.makedirs(out_dir)
    trace_dir = os.path.join(out_dir, "trace") if trace else None

    # the one shape serve() meets first inside its loop and not in its
    # warm-up: the stack of a batch's generated tokens
    jax.block_until_ready(jnp.stack([jnp.zeros((B,), jnp.int32)] * G,
                                    axis=1))

    sp = ServingProfiler(os.path.join(out_dir, "measure"),
                         rng_seed=pseed).start()
    hook = Hook(sp, seconds, trace_dir=trace_dir)
    with _CompileCounter() as compiles, seeded_weights(cell, pseed):
        try:
            serve(cfg, n_requests=B * ENDLESS_BATCHES, batch=B,
                  prompt_len=P, gen_len=G, seed=pseed, serving=hook)
        except WindowClosed:
            pass
        else:
            raise RuntimeError("serve() ended before the window closed")
    window_s = hook.t_stop - hook.t0
    setup_s = hook.t0 - t_start
    in_window_compiles = compiles.since(hook.t0)
    if trace_dir is not None:
        jax.profiler.stop_trace()

    # -- the database: flush, write, aggregate ----------------------------
    clock = harness.now
    t = clock()
    sp.profiler.flush()
    paths = sp.write()
    t_fw = clock()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    db = aggregate(profs, os.path.join(out_dir, "db"), trace_paths=traces)
    t_db = clock()
    sp.stop()

    windows = hook.windows
    db_mismatch = db_window_mismatch(db, windows)

    # -- what the timed path produced, to host memory ---------------------
    finished = [np.asarray(o) for o in hook.finished]
    prompts = {r: np.asarray(p) for r, p in hook.prompts.items()}
    hook.finished = hook.prompts = None
    device["memory_peak_bytes"] = harness.memory_peak_bytes(cell.chips)
    del hook, sp, db
    gc.collect()

    rows_p, rows_g = _finished_rows(finished, prompts, B)
    rng = np.random.default_rng(seed)
    n_sample = min(tr["sample_requests"], len(rows_p))
    pick = np.sort(rng.choice(len(rows_p), n_sample, replace=False)) \
        if n_sample else np.zeros(0, int)
    checks = [harness.Check("db_windows_mismatched", float(db_mismatch),
                            0.0, exact=True),
              harness.Check("finished_requests_sampled", float(n_sample),
                            float(tr["sample_requests"]), exact=True)]
    checked = (rows_p[pick], rows_g[pick])
    readings = {}
    if n_sample:
        readings = cell.reference.readings(cell.config, pseed, checked)
        checks += harness.limit_checks(cell, readings)

    host = {"windows": windows, "batch": B, "prompt_len": P, "gen_len": G,
            "flush_write_s": t_fw - t, "aggregate_s": t_db - t_fw,
            "db_build_s": t_db - t, "dispatches": len(windows),
            "window_compiles": in_window_compiles,
            "requests_finished": len(rows_p), "checked": checked,
            "readings": readings, "program_seed": pseed}
    attempted = B * sum(1 for w in windows if w[1] == "prefill")
    reduction = None
    if trace_dir is not None:
        from chipbench.trace import reduce_dir
        reduction = reduce_dir(trace_dir, chips=cell.chips)
    return harness.Run(cell=cell, seed=seed, setup_s=setup_s,
                       window_s=window_s, attempted=attempted, failed=0,
                       host=host, checks=checks, trace=reduction,
                       device=device)


def _finished_rows(finished: list, prompts: dict, batch: int):
    """(prompts, generated tokens) of every finished request, in order."""
    ps, gs = [], []
    for k, toks in enumerate(finished):
        lo = k * batch
        rid = f"r{lo}" if batch == 1 else f"r{lo}-r{lo + batch - 1}"
        ps.append(prompts[rid][:toks.shape[0]])
        gs.append(toks)
    if not ps:
        return np.zeros((0, 0), np.int32), np.zeros((0, 0), np.int32)
    return np.concatenate(ps), np.concatenate(gs)


def db_window_mismatch(db, windows) -> int:
    """How many (request, phase) pairs hold another number of device
    events in the database than the window dispatched (each window
    dispatches exactly once), pairs missing on either side included."""
    from repro.traceview.tracedb import TraceDB
    want: Dict[tuple, int] = {}
    for rid, phase, *_ in windows:
        want[(rid, phase)] = want.get((rid, phase), 0) + 1
    labels = _context_labels(db)
    got: Dict[tuple, int] = {}
    with TraceDB(db.trace_db_path()) as tdb:
        for td in tdb.line_views():
            if td.identity.get("type") != "gpu":
                continue
            for c in np.asarray(td.ctx, np.int64):
                key = labels[c] if 0 <= c < len(labels) else (None, None)
                got[key] = got.get(key, 0) + 1
    keys = set(want) | set(got)
    return sum(1 for k in keys if want.get(k, 0) != got.get(k, 0))


def _context_labels(db) -> List[tuple]:
    """(request id, phase) of each context: the nearest enclosing
    ``request:<id>`` and ``phase:<p>`` frames of the ``<serving>``
    module, read from the database's own tree."""
    parents = np.asarray(db.parents, np.int64)
    out: List[Optional[tuple]] = [None] * len(db.frames)
    for start in range(len(out)):
        chain, i = [], start
        while i >= 0 and out[i] is None:
            chain.append(i)
            i = int(parents[i])
        rid, phase = out[i] if i >= 0 else (None, None)
        for j in reversed(chain):
            fr = db.frames[j]
            if getattr(fr, "module", None) == "<serving>":
                if fr.name.startswith("request:"):
                    rid, phase = fr.name[len("request:"):], None
                elif fr.name.startswith("phase:"):
                    phase = fr.name[len("phase:"):]
            out[j] = (rid, phase)
    return out


class _CompileCounter:
    """Counts XLA compilations, by the time each finishes, while open."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __enter__(self):
        import jax
        self.times: List[float] = []
        jax.monitoring.register_event_duration_secs_listener(self._on)
        return self

    def __exit__(self, *exc):
        import jax
        jax.monitoring.unregister_event_duration_listener(self._on)
        return False

    def _on(self, event: str, duration: float, **_) -> None:
        if event == self.EVENT:
            self.times.append(harness.now())

    def since(self, t: float) -> int:
        return sum(1 for x in self.times if x >= t)
