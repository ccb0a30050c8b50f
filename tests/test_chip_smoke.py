"""``chip_smoke.py`` off the chip.

The script itself refuses to run anywhere but on a TPU, so here its
phases are driven directly, at small sizes on the CPU (kernels
interpreted, reduced configs): the control flow, the entry points they
call and the checks they make stay exercised between chip runs.  The
four-device phase runs in a child process that gives the CPU backend
four virtual devices.
"""
import importlib.util
import json
import os
import subprocess
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SCRIPT = os.path.join(REPO, "chip_smoke.py")


def _env(**extra):
    env = dict(os.environ, JAX_PLATFORMS="cpu", **extra)
    env["PYTHONPATH"] = os.path.join(REPO, "src") + (
        os.pathsep + env["PYTHONPATH"] if env.get("PYTHONPATH") else "")
    return env


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def test_refuses_without_a_tpu(tmp_path):
    proc = subprocess.run([sys.executable, SCRIPT], cwd=str(tmp_path),
                          env=_env(), capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    assert proc.stdout.strip() == ""        # no phase, no result line


def test_kernels_match_oracles(smoke):
    found = smoke.check_kernels({
        "flash_attention": dict(B=1, S=256, H=4, Hkv=2, D=128),
        "flash_decode": dict(B=2, Smax=1024, H=4, Hkv=2, D=128,
                             length=700),
        "ssm_scan": dict(B=1, S=512, nh=3, hd=64, st=16, chunk=256)})
    assert set(found) == {"flash_attention", "flash_decode", "ssm_scan"}
    for err, _custom_call in found.values():
        assert err <= smoke.BF16_TOL


def test_serving_profiled_equals_bare(smoke, tmp_path):
    from repro.configs import get_config
    s = smoke.check_serving(get_config("qwen2-1.5b").reduced(),
                            str(tmp_path), n_requests=8, batch=4,
                            prompt_len=16, gen_len=4)
    assert s["identical"] and s["flushed"] is True
    assert s["tokens"] == (8, 4)
    assert sorted(r for r, _, _ in s["rows"]) == ["r0-r3", "r4-r7"]
    assert all(p > 0 and d > 0 for _, p, d in s["rows"])


def test_training_saves_and_profiles(smoke, tmp_path):
    from repro.configs import get_config
    tr = smoke.check_training(get_config("xlstm-125m").reduced(),
                              str(tmp_path), seq=32, batch=2, steps=2)
    assert tr["finite"] and len(tr["losses"]) == 2
    assert tr["checkpoint_step"] == 2
    assert tr["profile_written"]


def test_sharded_training_on_four_devices(tmp_path):
    code = (
        "import json, chip_smoke\n"
        "from repro.configs import get_config\n"
        "r = chip_smoke.check_sharded_training(\n"
        "    get_config('qwen2-1.5b').reduced(), %r, seq=32,\n"
        "    global_batch=8, steps=2)\n"
        "print(json.dumps(r))\n" % str(tmp_path))
    proc = subprocess.run(
        [sys.executable, "-c", code], cwd=REPO, capture_output=True,
        text=True, timeout=600,
        env=_env(XLA_FLAGS="--xla_force_host_platform_device_count=4"))
    assert proc.returncode == 0, proc.stderr[-2000:]
    r = json.loads(proc.stdout.strip().splitlines()[-1])
    assert r["identical"] and np.all(np.isfinite(r["losses"]))
    shares = [b / r["param_bytes"] for b in r["per_device"].values()]
    assert len(shares) == 4 and all(0.2 < f < 0.3 for f in shares)
