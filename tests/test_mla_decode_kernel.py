"""Latent-attention decode Pallas kernel (interpret mode) against the
einsum path of ``models.mla.mla_decode``."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import get_config
from repro.kernels import ops
from repro.kernels.mla_decode import BATCH_BLOCK as BB
from repro.kernels.mla_decode import SLOT_BLOCK as BK
from repro.kernels.mla_decode import mla_decode_fwd
from repro.models import mla as mla_mod

L, H, R, ROPE, SMAX = 3, 4, 128, 64, 384
SCALE = 0.1
TOL = {jnp.float32: dict(rtol=2e-5, atol=2e-5),
       jnp.bfloat16: dict(rtol=2e-2, atol=2e-2)}


def _inputs(B, dtype, seed=0, smax=SMAX):
    ks = jax.random.split(jax.random.PRNGKey(seed), 6)
    return [jax.random.normal(k, s).astype(dtype) for k, s in zip(ks, [
        (B, H, R), (B, H, ROPE), (B, R), (B, ROPE), (L, B, smax, R),
        (L, B, smax, ROPE)])]


def _reference(q_lat, q_pe, c_new, pe_new, c_cache, pe_cache, pos, layer):
    return mla_mod._attend(q_lat, q_pe, c_new, pe_new, c_cache[layer],
                           pe_cache[layer], jnp.int32(pos), SCALE)


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("B", [BB, 2 * BB, BB + 4, 3],
                         ids=["one-block", "two-blocks", "ragged", "short"])
@pytest.mark.parametrize("pos", [0, 5, BK, BK + 60, SMAX - 1],
                         ids=["new-only", "first-block", "block-edge",
                              "mid-block", "last-slot"])
def test_kernel_matches_the_einsum_path(pos, B, dtype):
    """Every position class (the new token alone, inside the first
    block, on a block edge, mid-block, the last slot) and batch (one
    batch block, two, a ragged last block, fewer rows than a block), in
    f32 and bf16."""
    args = _inputs(B, dtype)
    got = mla_decode_fwd(*args, jnp.int32(pos), jnp.int32(1), scale=SCALE,
                         interpret=True)
    assert got.shape == (B, H, R) and got.dtype == dtype
    want = _reference(*args, pos, 1)
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


@pytest.mark.parametrize("pos", [1, BK + 60],
                         ids=["first-block", "mid-block"])
def test_slots_past_the_position_are_not_read(pos):
    """Large garbage in the slots from the position on, in the rest of
    the last block read and in the blocks after it, and in the other
    layers, leaves the output as it was."""
    args = _inputs(BB + 4, jnp.bfloat16)
    clean = mla_decode_fwd(*args, jnp.int32(pos), jnp.int32(2),
                           scale=SCALE, interpret=True)
    c_cache, pe_cache = args[4:]
    dirty = args[:4] + [
        c_cache.at[:, :, pos:].set(3e4).at[:2].set(-3e4),
        pe_cache.at[:, :, pos:].set(-3e4).at[:2].set(3e4)]
    got = mla_decode_fwd(*dirty, jnp.int32(pos), jnp.int32(2),
                         scale=SCALE, interpret=True)
    np.testing.assert_array_equal(np.asarray(got, np.float32),
                                  np.asarray(clean, np.float32))
    assert np.isfinite(np.asarray(got, np.float32)).all()


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16],
                         ids=["f32", "bf16"])
@pytest.mark.parametrize("smax", [200, 1256])
@pytest.mark.parametrize("pos", [5, BK, -1],
                         ids=["first-block", "block-edge", "last-slot"])
def test_slots_not_a_multiple_of_the_block(pos, smax, dtype):
    """A cache whose slots are not a multiple of the block: the last
    block reads past the cache's end (NaN in interpret mode, anything on
    the chip), and none of that reaches the output."""
    pos = smax - 1 if pos < 0 else pos
    args = _inputs(3, dtype, smax=smax)
    got = mla_decode_fwd(*args, jnp.int32(pos), jnp.int32(0), scale=SCALE,
                         interpret=True)
    want = _reference(*args, pos, 0)
    assert np.isfinite(np.asarray(got, np.float32)).all()
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), **TOL[dtype])


def test_decode_step_on_the_kernel_equals_the_einsum_path(monkeypatch):
    """``mla_decode`` with the kernel in place of its einsums (as on a
    TPU) gives the same output and rows, reading the layer it is given
    of the stacked caches."""
    cfg = get_config("moonlight-16b-a3b").reduced()
    p = mla_mod.init_mla_params(jax.random.PRNGKey(0), cfg, jnp.float32)
    B, smax, pos = 3, 256, 130
    h = jax.random.normal(jax.random.PRNGKey(1), (B, 1, cfg.d_model))
    ks = jax.random.split(jax.random.PRNGKey(2), 2)
    caches = [jax.random.normal(ks[0], (2, B, smax, cfg.kv_lora_rank)),
              jax.random.normal(ks[1], (2, B, smax, cfg.qk_rope_head_dim))]
    positions = jnp.full((B, 1), pos, jnp.int32)

    def run():
        return mla_mod.mla_decode(p, h, cfg, positions, *caches,
                                  jnp.int32(pos), jnp.int32(1))
    want, want_rows = run()
    monkeypatch.setattr(mla_mod, "_use_kernel", lambda: True)
    called = []
    kernel = ops.mla_decode
    monkeypatch.setattr(ops, "mla_decode",
                        lambda *a, **k: called.append(1) or kernel(*a, **k))
    got, rows = run()
    assert called
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)
    for a, b in zip(rows, want_rows):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
