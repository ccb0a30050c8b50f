"""Every metric the benchmark had before the program's own spans, and
the ``breakdown``, read from the small chip trace
(``testdata/small.xplane.pb``) and a fixed run around it: the readings
are pinned, so a change to the reduction or to a reader shows here."""
import os

import pytest

from chipbench import harness, trace
from chipbench.tests import tiny

SMALL = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "small.xplane.pb")

EXISTING = ("serve_tokens_per_s", "token_gap_p95_ms",
            "db_build_us_per_dispatch", "setup_s", "device_idle_pct.serve",
            "mfu.serve", "decode_step_roofline", "decode_launch_gap_us",
            "flush_write_s", "aggregate_s")


def pinned_run() -> harness.Run:
    """The trace's three prefill and five decode dispatches as one
    batch's windows, 10 ms apart, with fixed host readings."""
    cell = tiny.serve_cell()
    phases = ["prefill"] * 3 + ["decode"] * 5
    windows = [("r0-r63", p, i if p == "prefill" else i - 3,
                0.01 * i, 0.01 * i + 0.004 + 0.001 * (i % 3))
               for i, p in enumerate(phases)]
    host = {"windows": windows, "batch": 64, "prompt_len": 256,
            "gen_len": 1024, "flush_write_s": 0.5, "aggregate_s": 0.25,
            "db_build_s": 0.75, "dispatches": len(windows)}
    return harness.Run(cell=cell, seed=1, setup_s=12.5, window_s=0.08,
                       attempted=192, failed=0, host=host, checks=[],
                       trace=trace.reduce_trace(SMALL),
                       device={"count": 1},
                       peaks=harness.peaks_for("TPU v5 lite"))


READINGS = {
    "serve_tokens_per_s": 6400.0,
    "token_gap_p95_ms": 11.00000000000001,
    "db_build_us_per_dispatch": 93750.0,
    "setup_s": 12.5,
    "device_idle_pct.serve": 99.80516913977311,
    "mfu.serve": 0.10366472092847798,
    "decode_step_roofline": 43.66121348269579,
    "decode_launch_gap_us": 6436.23725,
    "flush_write_s": 0.5,
    "aggregate_s": 0.25,
}

BREAKDOWN = {
    "device_ops": [["fusion", 6.3025e-05],
                   ["convolution_tanh_fusion", 3.7859e-05],
                   ["copy-start", 1.05e-07],
                   ["copy-done", 2.3e-08]],
    "idle_gaps": [["chipbench:close (stats+tick)", 0.05133606400000001],
                  ["chipbench:dispatch+sync", 0.00040892]],
}


@pytest.fixture(scope="module")
def run():
    return pinned_run()


@pytest.mark.parametrize("name", EXISTING)
def test_existing_metric_reading_is_pinned(run, name):
    got = harness.metric_reader(name)(run)
    assert got == pytest.approx(READINGS[name], rel=1e-12, abs=0)


def test_breakdown_is_pinned(run):
    got = run.trace.breakdown()
    assert list(got) == list(BREAKDOWN)
    for key, rows in BREAKDOWN.items():
        assert [r[0] for r in got[key]] == [r[0] for r in rows]
        assert [float(r[1]) for r in got[key]] == pytest.approx(
            [r[1] for r in rows], rel=1e-12, abs=0)
