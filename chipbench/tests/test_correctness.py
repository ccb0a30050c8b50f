"""The comparison that decides ``correct``, driven through whole runs at
a tiny size on the CPU (the look for a chip skipped), with the timed path
sound and with each fault a serving cell can have planted underneath
(``faults.py``): each must come out as not correct."""
import time

import pytest

from chipbench import faults, harness
from chipbench.entries import serve
from chipbench.tests import tiny


def _serve(cell, tmp_path, seed, seconds=1.5):
    return serve.run(cell, seed=seed, seconds=seconds, trace=False,
                     t_start=time.perf_counter(), out_dir=str(tmp_path),
                     allow_cpu=True)


def test_serving_sound(tmp_path):
    run = _serve(tiny.serve_cell(), tmp_path, 2 ** 33 + 1)
    assert harness.all_ok(run.checks), run.checks


@pytest.mark.parametrize("fault,seed", [("token-altered", 2 ** 33 + 2),
                                        ("stale-cache", 2 ** 33 + 3)])
def test_serving_fault_in_the_model_step(tmp_path, fault, seed):
    cell = tiny.serve_cell()
    with faults.planted(fault):
        run = _serve(cell, tmp_path, seed)
    assert not harness.all_ok(run.checks)
    assert {c.name for c in run.checks if not c.ok} == set(
        cell.config["limits"])


def test_serving_dispatch_lost_by_the_profiler(tmp_path):
    with faults.planted("dispatch-lost"):
        run = _serve(tiny.serve_cell(), tmp_path, 2 ** 33 + 4)
    assert not harness.all_ok(run.checks)
    assert {c.name for c in run.checks if not c.ok} == {
        "db_windows_mismatched"}


def test_control_fails_where_the_program_passes(tmp_path):
    """The control (the reference in float8 in the program's place) at
    the configuration's own widths, two layers deep, with short requests:
    the program is correct and the control, through the same limits, is
    not.  On the chip, at the cells' sizes: ``run.py --seeds ...
    --control``."""
    cell = tiny.cell("qwen2-1.5b", "decode-reasoning", {"n_layers": 2},
                     batch=2, prompt_len=32, gen_len=24, sample_requests=2)
    run = _serve(cell, tmp_path, 2 ** 33 + 5, seconds=30.0)
    assert harness.all_ok(run.checks), run.checks
    control = cell.reference.control_readings(
        cell.config, run.host["program_seed"], run.host["checked"])
    assert not harness.all_ok(harness.limit_checks(cell, control)), control
