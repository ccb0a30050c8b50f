"""The readers of the program's own spans (``program_spans.py`` and the
six metrics over it), on a trace of a tiny serving run recorded on the
CPU, on the small chip trace that has none of them, and on spans and
idle gaps laid out by hand."""
import os
import shutil
import time

import numpy as np
import pytest

from chipbench import harness, program_spans, trace
from chipbench.entries import serve
from chipbench.tests import tiny
from chipbench.tests.test_pinned import SMALL, pinned_run

NEW = ("dispatch_tool_us", "window_close_us", "enqueue_us",
       "monitor_drain_us_per_dispatch", "idle_under_monitor_pct",
       "samples_kept_pct")


def _read(name, run):
    return harness.metric_reader(name)(run)


@pytest.fixture(scope="module")
def run(tmp_path_factory):
    """A tiny serving cell traced on the CPU, its files where ``run.py``
    would put them under a root of its own."""
    root = tmp_path_factory.mktemp("root")
    cell = tiny.serve_cell()
    cell.base = str(root / "chipbench")
    return serve.run(cell, seed=2 ** 33 + 17, seconds=1.5, trace=True,
                     t_start=time.perf_counter(),
                     out_dir=str(root / ".chipbench" / cell.name),
                     allow_cpu=True)


def test_spans_of_the_window(run):
    assert program_spans.of_run(run)
    decode = sum(1 for w in run.host["windows"] if w[1] == "decode")
    assert decode > 0
    for name in ("serving.close", "serving.open", "serve.enqueue",
                 "serve.sync"):
        assert len(program_spans.in_window(run, name, phase="decode")) \
            == decode, name
    assert len(program_spans.in_window(run, "serve.dispatch")) == \
        run.host["dispatches"]


def test_mean_span_readers(run):
    for metric, name in (("window_close_us", "serving.close"),
                         ("enqueue_us", "serve.enqueue")):
        spans = program_spans.in_window(run, name, phase="decode")
        want = np.mean([s.end_ns - s.start_ns for s in spans]) / 1e3
        assert _read(metric, run) == pytest.approx(want)
        assert _read(metric, run) > 0


def test_tick_counter_readers(run):
    ticks = program_spans.in_window(run, "serving.tick")
    first, last = ticks[0].args, ticks[-1].args
    assert last["dispatches"] - first["dispatches"] == len(ticks) - 1
    assert _read("dispatch_tool_us", run) == pytest.approx(
        (last["tool_ns"] - first["tool_ns"])
        / (last["dispatches"] - first["dispatches"]) / 1e3)
    assert _read("dispatch_tool_us", run) > 0
    kept = last["samples_kept"] - first["samples_kept"]
    dropped = last["samples_dropped"] - first["samples_dropped"]
    assert _read("samples_kept_pct", run) == pytest.approx(
        100 * kept / (kept + dropped))
    assert 0 < _read("samples_kept_pct", run) <= 100


def test_monitor_drain_per_dispatch(run):
    drains = program_spans.in_window(run, "monitor.drain")
    assert drains and all(d.args["activities"] <= d.args["records"]
                          for d in drains)
    assert _read("monitor_drain_us_per_dispatch", run) == pytest.approx(
        sum(d.end_ns - d.start_ns for d in drains)
        / run.host["dispatches"] / 1e3)


def test_idle_under_monitor_against_a_grid(run):
    # the CPU trace has no device: nothing to read
    assert _read("idle_under_monitor_pct", run) is None
    # a device busy exactly while the host syncs on it
    syncs = program_spans.in_window(run, "serve.sync")
    s = np.array([x.start_ns for x in syncs])
    e = np.array([x.end_ns for x in syncs])
    red = trace.Reduction(run.trace.t0_ns, run.trace.t1_ns, [
        trace.DeviceTrace(["op"] * len(s), s, e, [], np.zeros(0),
                          np.zeros(0))], [])
    fake = harness.Run(run.cell, run.seed, 0.0, run.window_s, 0, 0,
                       run.host, [], trace=red)
    got = _read("idle_under_monitor_pct", fake)
    # the same share on a 1 us grid
    t0 = red.t0_ns
    n = int((red.t1_ns - t0) // 1000)
    busy, drain = np.zeros(n, bool), np.zeros(n, bool)
    for mask, spans in ((busy, syncs),
                        (drain, program_spans.in_window(run,
                                                        "monitor.drain"))):
        for x in spans:
            mask[int((x.start_ns - t0) // 1000):
                 int((x.end_ns - t0) // 1000)] = True
    idle = ~busy
    assert got == pytest.approx(100 * (idle & drain).sum() / idle.sum(),
                                abs=0.5)


def test_a_program_without_spans_reads_nothing(tmp_path):
    run = pinned_run()
    run.cell.base = str(tmp_path / "chipbench")
    dest = tmp_path / ".chipbench" / run.cell.name / "trace"
    dest.mkdir(parents=True)
    shutil.copy(SMALL, dest)
    assert program_spans.of_run(run) == []
    for name in NEW:
        assert _read(name, run) is None, name


def _span(name, a, b, line="serving"):
    return program_spans.Span(name, a * 1e9, b * 1e9, {}, line)


def test_idle_split_by_innermost_span():
    # device busy in [4, 6) and [9, 12): idle [0, 4) and [6, 9)
    dev = trace.DeviceTrace(["op", "op"], np.array([4e9, 9e9]),
                            np.array([6e9, 12e9]), [], np.zeros(0),
                            np.zeros(0))
    red = trace.Reduction(0.0, 12e9, [dev], [])
    spans = [_span("serve.dispatch", 0, 10), _span("serve.enqueue", 2, 4),
             _span("serve.sync", 6, 8), _span("monitor.drain", 3, 7, "mon")]
    assert program_spans.idle_by_innermost(red, spans) == {
        "serve.dispatch": 3.0, "serve.enqueue": 2.0, "serve.sync": 2.0,
        program_spans.NO_SPAN: 0.0}
    assert program_spans.idle_ns(red) == 7e9
    assert program_spans.idle_under(red, spans[3:]) == 2e9
    assert program_spans.idle_under(red, spans) == 7e9
    assert program_spans.idle_under(red, []) == 0.0


def test_report_on_the_cpu_trace(run):
    rep = program_spans.report(program_spans.trace_dir(run))
    assert rep["spans"]["serve.dispatch"] >= run.host["dispatches"]
    assert rep["idle_s"] == 0.0 and rep["idle_by_innermost_span_s"] == {}
    assert sum(rep["level_residency"].values()) == pytest.approx(1.0)
    assert rep["clock"]["decode_enqueue_spans"] > 0


def test_clock_check_on_the_small_chip_trace():
    # the small trace's last five dispatch spans each enqueue, run and
    # sync one decode_step program: stand a decode serve.enqueue span at
    # the start of each and a serve.sync span over the rest
    red = trace.reduce_trace(SMALL)
    steps = sorted((s, e) for n, s, e in red.spans
                   if n == "chipbench:dispatch+sync")[-5:]
    spans = []
    for s, e in steps:
        spans += [program_spans.Span("serve.enqueue", s, s + 1.0,
                                     {"phase": "decode"}, "serving"),
                  program_spans.Span("serve.sync", s + 1.0, e,
                                     {"phase": "decode"}, "serving")]
    got = program_spans.clock_check(SMALL, red, spans)
    assert got["decode_enqueue_spans"] == 5
    assert got["enqueue_inside_step"] == 5
    assert got["program_starts_after_span_start"] == 5
    assert sum(got["enqueue_threads"].values()) == 5
    assert got["sync_idle_before_program_s"] >= 0
    assert got["sync_idle_after_program_s"] > 0
