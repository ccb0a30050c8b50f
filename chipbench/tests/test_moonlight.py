"""The moonlight cell at a tiny size on the CPU: the program against the
plain reference (prefill, then decode through the latent cache into its
last slot; one MoE layer's expert-parallel shares), and the comparison
that decides ``correct`` with the timed path sound, with each fault of
the model step planted, and with the control in its place."""
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from chipbench import faults, harness
from chipbench.entries import serve
from chipbench.tests import test_op_scopes, tiny

SEED = 2 ** 33 + 31          # a run's --seed
W_SEED = 2 ** 31 - 5         # a program seed, as harness.program_seed gives


def _cell(**program):
    cell = test_op_scopes.tiny_cell()
    cell.config["program"].update(program)
    return cell


def _serve(cell, tmp_path, seed, seconds=1.5):
    return serve.run(cell, seed=seed, seconds=seconds, trace=False,
                     t_start=time.perf_counter(), out_dir=str(tmp_path),
                     allow_cpu=True)


def test_weights_are_the_layers_the_reference_draws():
    cell = _cell()
    w = cell.reference.weights(cell.config, W_SEED)
    for i, (group, name) in enumerate([("lead", "d0"), ("layers", "e0")]):
        one = cell.reference.layer_weights(cell.config, W_SEED, i)
        got = jax.tree.map(lambda a: a[0], w[group][name])
        assert jax.tree.structure(got) == jax.tree.structure(one)
        for a, b in zip(jax.tree.leaves(got), jax.tree.leaves(one)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_prefill_then_decode_matches_the_reference_forward():
    """The program in float32 (prefill of 12 tokens, then 5 decode
    steps, the last into the cache's last slot) against the reference's
    full forward pass over the same tokens, logits compared."""
    from repro.launch.serve import _grow_cache
    from repro.models import transformer as T
    cell = _cell(dtype="float32")
    cfg = harness.program_config(cell.config)
    params = cell.reference.weights(cell.config, W_SEED)
    S, steps = 12, 5
    tokens = np.asarray(jax.random.randint(jax.random.PRNGKey(3),
                                           (2, S + steps), 0, cfg.vocab))
    want, _ = cell.reference.logits_at(cell.config, W_SEED, tokens, S - 1)
    opts = T.ModelOptions(q_chunk=4, kv_chunk=4)
    got, cache = T.prefill(params, cfg, jnp.asarray(tokens[:, :S]),
                           opts=opts)
    rows = [got]
    cache = _grow_cache(cfg, cache, 2, S + steps, S)
    step = jax.jit(lambda p, c, pos, tok: T.decode_step(
        p, cfg, c, token=tok, pos=pos, opts=opts), donate_argnums=(1,))
    for i in range(steps):
        got, cache = step(params, cache, jnp.int32(S + i),
                          jnp.asarray(tokens[:, S + i]))
        rows.append(got)
    np.testing.assert_allclose(np.stack(rows, 1), np.asarray(want),
                               rtol=2e-4, atol=2e-4)


@pytest.mark.parametrize("tokens", [24, 300])
def test_expert_parallel_shares_add_up_to_the_uncut_layer(tokens):
    """The 8 shares of a MoE layer (experts 8i..8i+7 each, all routing
    over the 64), their routed parts summed, plus the shared experts
    once, give the reference's uncut layer with all 64 experts: for a
    prefill's many tokens (grouped matmuls) and a decode step's few
    (every held expert, gated)."""
    from repro.models import moe as moe_mod
    from repro.models.layers import swiglu
    ref = test_op_scopes.tiny_cell().reference
    h = jax.random.normal(jax.random.PRNGKey(4), (1, tokens, 64))
    routed = 0.0
    for i in range(8):
        cell = _cell(dtype="float32", experts_first=8 * i)
        w = ref.layer_weights(cell.config, W_SEED, 1)
        y, load = moe_mod.held_moe(
            w["moe"], h[0], top_k=6, e_first=8 * i, scoring="sigmoid",
            routed_scale=2.446)
        assert int(load.sum()) == tokens * 6
        routed = routed + y
    shared = swiglu(h[0], w["shared"]["w1"], w["shared"]["w3"],
                    w["shared"]["w2"])
    whole = _cell(dtype="float32", experts_held=64)
    ww = ref.layer_weights(whole.config, W_SEED, 1)
    want, _ = ref._moe(h, ww, ref.sizes(whole.config), None)
    np.testing.assert_allclose(np.asarray(routed + shared),
                               np.asarray(want[0]), rtol=1e-4, atol=1e-5)
    # one share alone is not the whole layer
    assert not np.allclose(np.asarray(y + shared), np.asarray(want[0]),
                           rtol=1e-2, atol=1e-3)


@pytest.fixture(scope="module")
def sound(tmp_path_factory):
    cell = _cell()
    return cell, _serve(cell, tmp_path_factory.mktemp("sound"), SEED + 1)


def test_serving_sound(sound):
    _, run = sound
    assert harness.all_ok(run.checks), run.checks


def test_control_reads_wider_than_the_program(tmp_path):
    """The control (the reference in float8 in the program's place) at
    the configuration's own widths, two layers deep (the dense one and
    one MoE layer) and its vocabulary cut, with short requests: the
    program is correct, and the control's gaps are several times the
    program's on the same sequences.  Two layers do not take the
    control past the limits, which were set at all 27 on the chip, where
    it fails them (``run.py --seeds ... --control``; PERF.md §4)."""
    cell = tiny.cell("moonlight-16b-a3b", "decode-reasoning-b128",
                     {"n_layers": 2, "vocab": 8192}, batch=2,
                     prompt_len=32, gen_len=24, sample_requests=2)
    run = _serve(cell, tmp_path, SEED + 4, seconds=30.0)
    assert harness.all_ok(run.checks), run.checks
    program = run.host["readings"]
    control = cell.reference.control_readings(
        cell.config, run.host["program_seed"], run.host["checked"])
    print(program, control)
    for name in cell.config["limits"]:
        assert control[name] > 3 * program[name], (name, program, control)


@pytest.mark.parametrize("fault,seed", [("token-altered", SEED + 2),
                                        ("stale-cache", SEED + 3)])
def test_serving_fault_in_the_model_step(tmp_path, fault, seed):
    cell = _cell()
    with faults.planted(fault):
        run = _serve(cell, tmp_path, seed)
    failed = {c.name for c in run.checks if not c.ok}
    assert failed and failed <= set(cell.config["limits"])

