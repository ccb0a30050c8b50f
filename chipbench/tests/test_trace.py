"""The reduction from a trace to the per-layer numbers, on a small trace
recorded on a TPU v5e (``record_trace.py``): three executions of
``prefill_step`` and five of ``decode_step``, each followed by a 5 ms host
pause inside a ``close (stats+tick)`` span."""
import os

import numpy as np
import pytest

from chipbench import trace

SMALL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "small.xplane.pb")


@pytest.fixture(scope="module")
def red():
    return trace.reduce_trace(SMALL)


def test_programs_counted_by_jit_name(red):
    assert len(red.program_times("prefill_step")) == 3
    assert len(red.program_times("decode_step")) == 5
    assert len(red.program_times("train_step")) == 0


def test_busy_and_idle_share(red):
    assert 0 < red.busy_s < red.window_s
    assert red.idle_pct() == pytest.approx(
        100 * (1 - red.busy_s / red.window_s))
    # the device works a few microseconds per program: idle most of it
    assert red.idle_pct() > 90


def test_device_clock_put_after_host_enqueue(red):
    # each dispatched program runs inside the host span that dispatched
    # and synced it
    spans = sorted((s, e) for n, s, e in red.spans
                   if n == "chipbench:dispatch+sync")
    d = red.devices[0]
    mine = [trace.program_name(n) in ("prefill_step", "decode_step")
            for n in d.prog_names]
    starts = np.sort(d.prog_start[mine])
    assert len(spans) == len(starts) == 8
    assert np.all(starts >= [s for s, _ in spans])
    assert np.all(starts <= [e for _, e in spans])


def test_gaps_between_and_their_label(red):
    gaps = red.gaps_between("decode_step", unless=("prefill_step",))
    assert len(gaps) == 4
    assert np.all(gaps > 0.005)              # the 5 ms pause in each
    assert len(red.gaps_between("decode_step")) == 4
    b = red.breakdown()
    labels = [k for k, _ in b["idle_gaps"]]
    assert labels[0] == "chipbench:close (stats+tick)"
    assert len(b["device_ops"]) <= 10
    assert all(v > 0 for _, v in b["device_ops"])


def test_union_intervals_and_self_times():
    s = np.array([0., 5., 1., 20.])
    e = np.array([10., 7., 3., 25.])
    us, ue = trace.union_intervals(s, e)
    assert us.tolist() == [0., 20.] and ue.tolist() == [10., 25.]
    assert trace.covered(us, ue, 5., 22.) == 7.
    own = trace.self_times(s, e)
    assert own.tolist() == [6., 2., 2., 5.]


def test_device_shift_pairs_by_run_id():
    enq = {"1": (0., 100.), "2": (500., 520.)}
    ex = {"1": (40., 60.), "2": (400., 450.)}
    assert trace.device_shift(enq, ex) == 120.
    assert trace.device_shift({}, ex) == 0.


def test_program_and_op_names():
    assert trace.program_name("jit_decode_step(7242)") == "decode_step"
    assert trace.op_name("%fusion.3 = bf16[2]{0} fusion(%a)") == "fusion.3"
