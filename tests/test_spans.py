"""Program spans (``repro.core.spans``) on a ``jax.profiler`` trace of a
tiny profiled ``serve()``: how they nest, the args they carry, and that
they cost no recording with no trace active."""
import collections
import glob
import os

import jax
import pytest
from jax.profiler import ProfileData

from repro.configs import get_config
from repro.core import spans
from repro.launch.serve import serve
from repro.serving import GovernorConfig, ServingProfiler

COUNTERS = ("tool_ns", "app_ns", "dispatches", "samples_kept",
            "samples_dropped", "deferred_ns")
N_REQUESTS, BATCH, GEN_LEN = 4, 2, 4
N_BATCHES = N_REQUESTS // BATCH
N_DECODE = N_BATCHES * (GEN_LEN - 1)
N_DISPATCH = N_BATCHES + N_DECODE

Span = collections.namedtuple("Span", "name start end args line")


def _record(trace_dir, body):
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(trace_dir), profiler_options=opts)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path, = glob.glob(os.path.join(str(trace_dir), "**", "*.xplane.pb"),
                      recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for li, line in enumerate(plane.lines):
            for ev in line.events:
                if ev.name.startswith(spans.PREFIX):
                    out.append(Span(ev.name[len(spans.PREFIX):],
                                    ev.start_ns,
                                    ev.start_ns + ev.duration_ns,
                                    {k: v for k, v in ev.stats},
                                    (plane.name, li)))
    return sorted(out, key=lambda s: s.start)


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    """Two batches served under a ``ServingProfiler``, traced from the
    first window to a drained profiler."""
    tmp = tmp_path_factory.mktemp("spans")
    cfg = get_config("qwen2-1.5b").reduced()
    sp = ServingProfiler(str(tmp / "measure"),
                         governor=GovernorConfig(interval=2),
                         sample_rate_hz=1e6).start()
    counters = {}

    def body():
        serve(cfg, n_requests=N_REQUESTS, batch=BATCH, prompt_len=8,
              gen_len=GEN_LEN, serving=sp)
        sp.profiler.flush()
        counters.update(sp.profiler.overhead_counters())
    try:
        got = _record(tmp / "trace", body)
    finally:
        sp.stop()
    return got, counters


def _named(got, name, **args):
    return [s for s in got if s.name == name
            and all(s.args.get(k) == v for k, v in args.items())]


def _inside(outer, got, name):
    return [s for s in _named(got, name) if s.line == outer.line
            and outer.start <= s.start and s.end <= outer.end]


def test_each_decode_window_closes_with_one_tick_and_observe(traced):
    got, _ = traced
    closes = _named(got, "serving.close", phase="decode")
    assert len(closes) == N_DECODE
    assert len(_named(got, "serving.open", phase="decode")) == N_DECODE
    for close in closes:
        assert len(_inside(close, got, "serving.stats")) == 1
        tick, = _inside(close, got, "serving.tick")
        assert len(_inside(tick, got, "governor.observe")) == 1
        assert len(_inside(tick, got, "serving.p99")) == 1


def test_each_dispatch_holds_its_enqueue_then_sync(traced):
    got, counters = traced
    dispatches = _named(got, "serve.dispatch")
    assert len(dispatches) == counters["dispatches"] == N_DISPATCH
    assert len(_named(got, "serve.dispatch", phase="prefill")) == N_BATCHES
    for d in dispatches:
        enq, = _inside(d, got, "serve.enqueue")
        sync, = _inside(d, got, "serve.sync")
        assert enq.end <= sync.start
        assert enq.args["phase"] == sync.args["phase"] == d.args["phase"]
    # the loop's own work between dispatches has spans of its own
    assert len(_named(got, "serve.next_token")) == N_DISPATCH
    assert len(_named(got, "serve.inputs")) == N_BATCHES
    assert len(_named(got, "serve.grow_cache")) == N_BATCHES


def test_monitor_drains_account_for_every_dispatch(traced):
    got, counters = traced
    drains = _named(got, "monitor.drain")
    assert sum(d.args["activities"] for d in drains) == N_DISPATCH
    assert sum(d.args["records"] for d in drains) == 2 * N_DISPATCH
    # the monitor works on a thread of its own, and the deferred draws
    # of every decode and prefill run inside its drains
    serving_lines = {d.line for d in _named(got, "serve.dispatch")}
    assert not serving_lines & {d.line for d in drains}
    draws = _named(got, "sampling.draw")
    assert len(draws) == N_DISPATCH
    for draw in draws:
        assert any(d.line == draw.line and d.start <= draw.start
                   and draw.end <= d.end for d in drains)
    assert sum(d.args["samples"] for d in draws) == \
        counters["samples_kept"]


def test_tick_counters_never_decrease(traced):
    got, counters = traced
    ticks = _named(got, "serving.tick")
    assert len(ticks) == N_DISPATCH
    for t in ticks:
        assert set(t.args) == set(COUNTERS) | {"level"}
        assert all(isinstance(t.args[k], int) for k in t.args)
    for k in COUNTERS:
        seq = [t.args[k] for t in ticks]
        assert seq == sorted(seq), k
    assert ticks[-1].args["dispatches"] == counters["dispatches"]
    assert ticks[-1].args["tool_ns"] <= counters["tool_ns"]


def test_no_trace_no_span(tmp_path):
    assert not jax.profiler.TraceAnnotation.is_enabled()
    s = spans.span("unrecorded", value=1)
    assert s is spans.OFF and spans.span("other") is spans.OFF
    with s as live:
        assert live is None

    def body():
        with spans.span("recorded", value=2) as live:
            assert live is not None
            live.set_metadata(after=3)
    got = _record(tmp_path, body)
    assert [(s.name, s.args) for s in got] == [
        ("recorded", {"value": 2, "after": 3})]
    with spans.span("unrecorded") as live:
        assert live is None
