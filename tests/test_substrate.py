"""Data pipeline, optimizer, gradient compression, sharding plan units."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis_compat import given, settings, st

from repro.configs import SHAPES, get_config
from repro.configs.base import ShapeConfig
from repro.data.pipeline import Prefetcher, SyntheticLM
from repro.distributed import compression as comp
from repro.distributed import sharding as shard_mod
from repro.optim import adamw


# ---------------------------------------------------------------------------
# data
# ---------------------------------------------------------------------------
def test_batch_deterministic():
    cfg = get_config("qwen2-1.5b").reduced()
    shape = ShapeConfig("t", 32, 4, "train")
    ds = SyntheticLM(cfg, shape, seed=1)
    a = ds.batch_at(7)
    b = ds.batch_at(7)
    np.testing.assert_array_equal(a["tokens"], b["tokens"])
    c = ds.batch_at(8)
    assert not np.array_equal(a["tokens"], c["tokens"])


def test_host_sharding_disjoint():
    cfg = get_config("qwen2-1.5b").reduced()
    shape = ShapeConfig("t", 16, 8, "train")
    h0 = SyntheticLM(cfg, shape, seed=1, n_hosts=2, host_id=0).batch_at(3)
    h1 = SyntheticLM(cfg, shape, seed=1, n_hosts=2, host_id=1).batch_at(3)
    assert h0["tokens"].shape == (4, 16)
    assert not np.array_equal(h0["tokens"], h1["tokens"])


def test_labels_are_next_token():
    cfg = get_config("qwen2-1.5b").reduced()
    ds = SyntheticLM(cfg, ShapeConfig("t", 16, 2, "train"), seed=0)
    b = ds.batch_at(0)
    np.testing.assert_array_equal(b["tokens"][:, 1:], b["labels"][:, :-1])


def test_prefetcher_ordered():
    cfg = get_config("qwen2-1.5b").reduced()
    ds = SyntheticLM(cfg, ShapeConfig("t", 8, 2, "train"), seed=0)
    pf = Prefetcher(ds, start_step=5)
    steps = [next(pf)[0] for _ in range(4)]
    pf.close()
    assert steps == [5, 6, 7, 8]


def test_vlm_batch_masks_frontend_labels():
    cfg = get_config("llava-next-mistral-7b").reduced()
    ds = SyntheticLM(cfg, ShapeConfig("t", 16, 2, "train"), seed=0)
    b = ds.batch_at(0)
    F = b["embeds"].shape[1]
    assert (b["labels"][:, :F] == -100).all()


# ---------------------------------------------------------------------------
# optimizer
# ---------------------------------------------------------------------------
def test_adamw_decreases_quadratic():
    cfg = adamw.OptConfig(peak_lr=0.1, warmup_steps=0, total_steps=100,
                          weight_decay=0.0)
    params = {"x": jnp.array([3.0, -2.0])}
    state = adamw.init(params)

    @jax.jit
    def step(p, s):
        loss, g = jax.value_and_grad(
            lambda p_: jnp.sum(p_["x"] ** 2))(p)
        p2, s2, m = adamw.update(cfg, g, s, p)
        return p2, s2, loss

    losses = []
    for _ in range(50):
        params, state, loss = step(params, state)
        losses.append(float(loss))
    assert losses[-1] < 0.1 * losses[0]


def test_grad_clipping_reported_norm():
    cfg = adamw.OptConfig(clip_norm=1.0, warmup_steps=0)
    params = {"x": jnp.zeros(3)}
    state = adamw.init(params)
    huge = {"x": jnp.full(3, 1e6)}
    p1, _, m = adamw.update(cfg, huge, state, params)
    assert float(m["grad_norm"]) > 1e5  # reported pre-clip norm
    # clipped update: same step as a grad of global-norm 1 in that direction
    unit = {"x": jnp.full(3, 1.0 / np.sqrt(3.0))}
    p2, _, _ = adamw.update(cfg, unit, adamw.init(params), params)
    np.testing.assert_allclose(np.asarray(p1["x"]), np.asarray(p2["x"]),
                               rtol=1e-5, atol=1e-7)


def test_schedule_warmup_and_decay():
    cfg = adamw.OptConfig(peak_lr=1.0, warmup_steps=10, total_steps=100,
                          min_lr_frac=0.1)
    lr0 = float(adamw.schedule(cfg, jnp.int32(0)))
    lr5 = float(adamw.schedule(cfg, jnp.int32(5)))
    lr10 = float(adamw.schedule(cfg, jnp.int32(10)))
    lr100 = float(adamw.schedule(cfg, jnp.int32(100)))
    assert lr0 == 0.0 and lr5 == pytest.approx(0.5)
    assert lr10 == pytest.approx(1.0)
    assert lr100 == pytest.approx(0.1, rel=1e-3)


# ---------------------------------------------------------------------------
# gradient compression
# ---------------------------------------------------------------------------
def test_int8_roundtrip_error_small():
    rng = np.random.default_rng(0)
    g = jnp.asarray(rng.standard_normal((64, 64)), jnp.float32)
    q, scale = comp.quantize(g)
    back = comp.dequantize(q, scale, g.shape, g.dtype)
    err = np.abs(np.asarray(back - g))
    # per-block bound: |err| <= scale/2 per element
    bound = np.repeat(np.asarray(scale), comp.BLOCK)[:g.size].reshape(
        g.shape) / 2 + 1e-6
    assert (err <= bound).all()


def test_error_feedback_converges():
    """EF compensation: mean of compressed grads -> true grad."""
    rng = np.random.default_rng(1)
    g = jnp.asarray(rng.standard_normal((512,)), jnp.float32)
    ef = jnp.zeros_like(g)
    acc = jnp.zeros_like(g)
    for _ in range(200):
        out, ef = comp.ef_compress(g, ef)
        acc = acc + out
    np.testing.assert_allclose(np.asarray(acc / 200), np.asarray(g),
                               atol=0.02)


def test_compressed_psum_matches_plain():
    from jax.sharding import PartitionSpec as P
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh((1, 1))
    x = jnp.asarray(np.random.default_rng(2).standard_normal((2, 256)),
                    jnp.float32)
    f = jax.shard_map(lambda v: comp.compressed_psum(v, "data"), mesh=mesh,
                      in_specs=P(), out_specs=P())
    np.testing.assert_allclose(np.asarray(f(x)), np.asarray(x), rtol=2e-2,
                               atol=2e-2)


def test_ef_compress_tree_shapes():
    tree = {"a": jnp.ones((8, 8)), "b": {"c": jnp.ones((4,))}}
    out = comp.ef_compress_tree(tree)
    assert jax.tree.structure(out) == jax.tree.structure(tree)
    for a, b in zip(jax.tree.leaves(out), jax.tree.leaves(tree)):
        assert a.shape == b.shape and a.dtype == b.dtype


# ---------------------------------------------------------------------------
# sharding plan
# ---------------------------------------------------------------------------
def test_plan_on_trivial_mesh():
    from repro.launch.mesh import make_test_mesh
    mesh = make_test_mesh()
    plan = shard_mod.make_plan(mesh)
    assert plan.model_axis == "model"
    assert plan.batch_spec() == jax.sharding.PartitionSpec(("data",))


def test_param_specs_divisibility_guard():
    """Non-divisible dims fall back to replication (explicit in_shardings
    must divide exactly)."""
    from repro.launch.mesh import make_test_mesh
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_test_mesh()
    plan = shard_mod.make_plan(mesh)
    from repro.models import transformer as T
    p = jax.eval_shape(lambda k: T.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    sh = shard_mod.param_shardings(p, cfg, plan)
    for leaf, s in zip(jax.tree.leaves(p), jax.tree.leaves(sh)):
        for dim, names in zip(leaf.shape, s.spec + (None,) * 4):
            if names is None:
                continue
            n = np.prod([mesh.shape[a] for a in
                         (names if isinstance(names, tuple) else (names,))])
            assert dim % n == 0


def test_dp_only_strategy_replicates():
    from repro.launch.mesh import make_test_mesh
    cfg = get_config("qwen2-1.5b").reduced()
    mesh = make_test_mesh()
    plan = shard_mod.make_plan(mesh, strategy="dp_only")
    from repro.models import transformer as T
    p = jax.eval_shape(lambda k: T.init_params(k, cfg),
                       jax.random.PRNGKey(0))
    sh = shard_mod.param_shardings(p, cfg, plan)
    for s in jax.tree.leaves(sh):
        # P() and P(None, ..., None) are the same sharding
        assert all(ax is None for ax in s.spec)
