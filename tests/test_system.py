"""System-level integration: train driver (with checkpoint/resume), serve
driver (with the §8.4 derived-metric workflow), trace format, dry-run units.
"""
import glob
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.models import transformer as T


OPTS = T.ModelOptions(q_chunk=16, kv_chunk=16, ssm_chunk=8, loss_chunk=16)


def test_train_driver_runs_and_checkpoints(tmp_path):
    from repro.launch.train import train
    cfg = get_config("xlstm-125m").reduced()
    shape = ShapeConfig("t", 32, 2, "train")
    _, hist, _ = train(cfg, shape, n_steps=4, ckpt_dir=str(tmp_path),
                       ckpt_every=2, opts=OPTS, log_every=1)
    assert all(np.isfinite(h["loss"]) for h in hist)
    from repro.checkpoint import CheckpointManager
    assert CheckpointManager(str(tmp_path)).latest_step() == 4


def test_train_driver_resume_continues(tmp_path):
    from repro.launch.train import train
    cfg = get_config("qwen2-1.5b").reduced()
    shape = ShapeConfig("t", 32, 2, "train")
    train(cfg, shape, n_steps=3, ckpt_dir=str(tmp_path), ckpt_every=3,
          opts=OPTS, log_every=1)
    # resume: starts from step 3, runs to 5
    _, hist, _ = train(cfg, shape, n_steps=5, ckpt_dir=str(tmp_path),
                       ckpt_every=5, opts=OPTS, resume=True, log_every=1)
    assert hist[0]["step"] >= 3


def test_train_driver_deterministic_data(tmp_path):
    """Same seed -> identical loss trajectory (restart reproducibility)."""
    from repro.launch.train import train
    cfg = get_config("xlstm-125m").reduced()
    shape = ShapeConfig("t", 32, 2, "train")
    _, h1, _ = train(cfg, shape, n_steps=3, opts=OPTS, seed=9, log_every=1)
    _, h2, _ = train(cfg, shape, n_steps=3, opts=OPTS, seed=9, log_every=1)
    assert [h["loss"] for h in h1] == pytest.approx(
        [h["loss"] for h in h2], rel=1e-6)


def test_train_with_profiling(tmp_path):
    from repro.launch.train import train
    cfg = get_config("xlstm-125m").reduced()
    shape = ShapeConfig("t", 32, 2, "train")
    _, _, paths = train(cfg, shape, n_steps=2, opts=OPTS,
                        profile_dir=str(tmp_path / "prof"), log_every=1)
    assert paths and "cpu_0" in paths
    from repro.core.profmt import read_profile
    p = read_profile(paths["cpu_0"])
    inv = p.metrics.index("gpu_kernel/invocations")
    assert sum(v for m, v in zip(p.value_mids, p.values) if m == inv) == 2
    assert any(f.kind == "gpu_op" for f in p.frames), \
        "fine-grained attribution below the train_step placeholder"


def test_serve_driver_and_sync_diff(tmp_path):
    """§8.4.1 reproduction: redundant syncs found via derived metric."""
    from repro.launch.serve import serve
    from repro.core.aggregate import aggregate
    from repro.core.derived import SYNC_DIFF, database_columns
    cfg = get_config("qwen2-1.5b").reduced()
    toks, paths = serve(cfg, n_requests=2, batch=2, prompt_len=16,
                        gen_len=4, profile_dir=str(tmp_path / "prof"),
                        redundant_sync=True)
    assert toks.shape == (2, 4)
    profs = [v for k, v in paths.items()
             if k.startswith("cpu_") and "trace" not in k]
    db = aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1)
    cols = database_columns(db)
    diff = SYNC_DIFF.evaluate(cols)
    # the global root shows sync_count > kernel_count
    assert diff[0] > 0, "redundant syncs must be visible in the derived metric"


def test_trace_out_of_order_sorted(tmp_path):
    from repro.core.trace import TraceWriter, read_trace
    p = str(tmp_path / "t.rtrc")
    tw = TraceWriter(p, {"rank": 0})
    tw.append(100, 110, 1)
    tw.append(50, 60, 2)    # out of order (§4.4)
    tw.append(200, 210, 3)
    tw.close()
    assert tw.out_of_order
    td = read_trace(p)
    assert list(td.starts) == [50, 100, 200]


def test_input_specs_all_cells_no_alloc():
    """input_specs builds ShapeDtypeStructs for every applicable cell
    without touching devices."""
    from repro.configs import list_configs
    from repro.configs.base import shape_applicable
    from repro.launch.specs import input_specs
    for arch in list_configs():
        cfg = get_config(arch)
        for sname, shape in SHAPES.items():
            if not shape_applicable(cfg, shape)[0]:
                continue
            specs = input_specs(cfg, shape, plan=None)
            leaves = jax.tree.leaves(specs,
                                     is_leaf=lambda x: isinstance(
                                         x, jax.ShapeDtypeStruct))
            assert leaves
            for leaf in leaves:
                assert isinstance(leaf, jax.ShapeDtypeStruct), (arch, sname)
            if shape.kind == "train":
                b = specs["batch"]
                total = (b["tokens"].shape if "tokens" in b
                         else b["embeds"].shape)
                assert total[0] == shape.global_batch


def test_model_flops_convention():
    from repro.core.roofline import model_flops
    cfg = get_config("qwen2-1.5b")
    tr = model_flops(cfg, SHAPES["train_4k"])
    pf = model_flops(cfg, SHAPES["prefill_32k"])
    dc = model_flops(cfg, SHAPES["decode_32k"])
    n = cfg.n_active_params()
    assert tr == pytest.approx(6 * n * 4096 * 256)
    assert pf == pytest.approx(2 * n * 32768 * 32)
    assert dc == pytest.approx(2 * n * 128)


def test_peak_table_is_keyed_by_device_kind():
    from repro.core.peaks import device_peaks, peaks_for
    v5e = peaks_for("TPU v5 lite")
    assert (v5e.flops, v5e.hbm_bw) == (197e12, 819e9)
    with pytest.raises(KeyError, match="TPU v9"):
        peaks_for("TPU v9")       # an unknown chip is an error
    assert device_peaks() is v5e  # the CPU backend stands in for a v5e


def test_roofline_report_terms():
    from repro.core.peaks import peaks_for
    from repro.core.roofline import analyze
    hlo = "HloModule m\n\nENTRY %main (x: f32[8]) -> f32[8] {\n" \
          "  ROOT %x = f32[8]{0} parameter(0)\n}\n"
    rep = analyze("t", "mesh", 4, {"flops": 197e12, "bytes accessed": 0.0},
                  peaks_for("TPU v5 lite"), hlo_text=hlo,
                  model_flops_total=4 * 197e12)
    assert rep.t_compute == pytest.approx(1.0)
    assert rep.dominant == "compute"
    assert rep.mfu == pytest.approx(1.0)
    assert rep.useful_ratio == pytest.approx(1.0)


def test_compile_cache_follows_the_environment(monkeypatch):
    """Placed from outside when JAX_COMPILATION_CACHE_DIR is set (and
    then nothing is set in code), else a fixed directory in the
    checkout."""
    from repro.launch import compile_cache
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.CACHE_DIR == os.path.join(repo, ".jax_cache")
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "placed-outside")
    assert compile_cache.enable_compile_cache() == "placed-outside"
    assert jax.config.jax_compilation_cache_dir == before
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        assert compile_cache.enable_compile_cache() == compile_cache.CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == compile_cache.CACHE_DIR
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
