"""Checkpoint manager (atomic/async/sharded/elastic) + fault tolerance."""
import json
import os
import subprocess
import sys
import textwrap
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.checkpoint import CheckpointManager
from repro.ft import (RestartPolicy, StragglerWatchdog, plan_elastic_mesh)


def tree():
    return {"params": {"w": jnp.arange(12.0).reshape(3, 4),
                       "b": jnp.ones((4,))},
            "opt": {"step": jnp.int32(7)}}


def test_roundtrip(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(10, tree())
    step, restored = mgr.restore(tree())
    assert step == 10
    np.testing.assert_allclose(restored["params"]["w"],
                               np.arange(12.0).reshape(3, 4))
    assert int(restored["opt"]["step"]) == 7


def test_async_save_and_wait(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(1, tree(), block=False)
    mgr.wait()
    assert mgr.latest_step() == 1


def test_async_save_failure_raises_at_wait(tmp_path, monkeypatch):
    """A background save that fails must not pass for a saved step."""
    mgr = CheckpointManager(str(tmp_path))

    def full_disk(*a, **k):
        raise OSError("no space left on device")
    monkeypatch.setattr(np, "save", full_disk)
    mgr.save(1, tree(), block=False)
    with pytest.raises(RuntimeError) as err:
        mgr.wait()
    assert isinstance(err.value.__cause__, OSError)
    assert mgr.latest_step() is None
    mgr.wait()                  # the failure is reported once


def test_atomicity_tmp_never_visible(tmp_path):
    mgr = CheckpointManager(str(tmp_path))
    mgr.save(3, tree())
    # a stale tmp dir (crashed writer) must be invisible to restore
    os.makedirs(str(tmp_path / "step_00000009.tmp"))
    assert mgr.latest_step() == 3
    step, _ = mgr.restore(tree())
    assert step == 3


def test_gc_keeps_last_k(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=2)
    for s in (1, 2, 3, 4):
        mgr.save(s, tree())
    assert mgr.all_steps() == [3, 4]


def test_restore_specific_step(tmp_path):
    mgr = CheckpointManager(str(tmp_path), keep=0)
    t = tree()
    mgr.save(1, t)
    t2 = {"params": {"w": t["params"]["w"] * 2, "b": t["params"]["b"]},
          "opt": {"step": jnp.int32(8)}}
    mgr.save(2, t2)
    step, restored = mgr.restore(tree(), step=1)
    np.testing.assert_allclose(restored["params"]["w"],
                               np.arange(12.0).reshape(3, 4))


ELASTIC_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.checkpoint import CheckpointManager

d = %r
from repro.launch.mesh import make_mesh
mesh8 = make_mesh((8,), ("data",))
sh8 = NamedSharding(mesh8, P("data"))
x = jax.device_put(jnp.arange(64.0).reshape(8, 8), sh8)
mgr = CheckpointManager(d)
mgr.save(5, {"x": x})
assert len(x.addressable_shards) == 8

# elastic restore onto a DIFFERENT mesh shape (2 x 4, sharded both dims)
mesh24 = make_mesh((2, 4), ("a", "b"))
sh24 = NamedSharding(mesh24, P("a", "b"))
step, out = mgr.restore({"x": jax.ShapeDtypeStruct((8, 8), jnp.float32)},
                        shardings={"x": sh24})
assert step == 5
np.testing.assert_allclose(np.asarray(out["x"]),
                           np.arange(64.0).reshape(8, 8))
assert out["x"].sharding == sh24
print("ELASTIC_OK")
"""


def test_elastic_restore_across_meshes(tmp_path):
    """Save on an (8,) mesh, restore onto (2,4) — different sharding."""
    env = dict(os.environ, PYTHONPATH="src")
    proc = subprocess.run(
        [sys.executable, "-c", ELASTIC_SCRIPT % str(tmp_path / "ck")],
        capture_output=True, text=True, env=env,
        cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
        timeout=300)
    assert "ELASTIC_OK" in proc.stdout, proc.stderr[-2000:]


# ---------------------------------------------------------------------------
# fault tolerance
# ---------------------------------------------------------------------------
def test_watchdog_stale_host():
    t = [0.0]
    wd = StragglerWatchdog(stale_s=10, lag_steps=5, clock=lambda: t[0])
    for h in ("h0", "h1", "h2"):
        wd.beat(h, 1)
    t[0] = 20.0
    wd.beat("h0", 2)
    wd.beat("h1", 2)
    assert wd.stragglers() == ["h2"]


def test_watchdog_lagging_host():
    t = [0.0]
    wd = StragglerWatchdog(stale_s=1e9, lag_steps=5, clock=lambda: t[0])
    for step in range(12):
        t[0] += 1
        wd.beat("h0", step)
        wd.beat("h1", step)
        wd.beat("h2", step // 4)  # lags
    assert "h2" in wd.stragglers()


def test_watchdog_slow_hosts():
    t = [0.0]
    wd = StragglerWatchdog(clock=lambda: t[0])
    for step in range(10):
        for h, dt in (("h0", 1.0), ("h1", 1.0), ("h2", 3.0)):
            wd.beat(h, step, t=step * dt)
    assert wd.slow_hosts(factor=1.5) == ["h2"]


def test_restart_policy_budget_and_backoff():
    rp = RestartPolicy(max_restarts=3, window_s=100, backoff_base_s=5,
                       backoff_max_s=40)
    for i in range(3):
        rp.record_failure(float(i))
        assert rp.should_restart(float(i))
    assert rp.backoff_s() == 20  # 5 * 2**2
    rp.record_failure(3.0)
    assert not rp.should_restart(3.5)
    # outside the window the budget refills
    assert rp.should_restart(1000.0)
    for _ in range(5):
        rp.record_failure(1000.0)
    assert rp.backoff_s() == 40  # capped


def test_elastic_plan_shrinks_data_axis():
    p = plan_elastic_mesh(256 - 16, model=16, old_data=16)
    assert p.mesh_shape == (8, 16)
    assert p.global_batch_scale == pytest.approx(0.5)


def test_elastic_plan_multipod_collapse():
    # half a pod dies: 2x16x16=512 -> 384 devices; pods collapse to 1
    p = plan_elastic_mesh(384, model=16, pods=2, old_data=16)
    assert p.mesh_shape[-1] == 16
    total = int(np.prod(p.mesh_shape))
    assert total <= 384
    assert p.mesh_axes[-1] == "model"


def test_elastic_plan_keeps_tp():
    with pytest.raises(AssertionError):
        plan_elastic_mesh(8, model=16)
