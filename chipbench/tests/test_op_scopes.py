"""The readers of the latent attention's and the held experts' device
time, and of the held experts' load, on a small chip trace of the tiny
moonlight cell (``testdata/scopes.xplane.pb``, ``record_scopes.py``):
the scope of each operation is decoded from the trace's own metadata,
and the readings are pinned."""
import os
import shutil

import pytest

from chipbench import harness, op_scopes, program_spans, trace
from chipbench.tests import tiny

SCOPES = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "testdata", "scopes.xplane.pb")
SIZES = dict(n_layers=2, d_model=64, n_heads=4, n_kv_heads=4, d_ff=128,
             vocab=256, head_dim=24, kv_lora_rank=32, qk_nope_head_dim=16,
             qk_rope_head_dim=8, v_head_dim=16)
PROMPT, STEPS = 16, 5


def tiny_cell():
    """The published configuration with every width cut and two layers
    (the dense one and one MoE layer, 8 of 64 experts held)."""
    cell = tiny.cell("moonlight-16b-a3b", "decode-reasoning-b128", SIZES,
                     batch=4, prompt_len=PROMPT, gen_len=STEPS + 1,
                     sample_requests=2)
    cell.name = "tiny-scopes"
    return cell


@pytest.fixture()
def run(tmp_path, monkeypatch):
    """The trace's five decode steps as one batch's windows, with the
    tiny cell's sizes; its trace found where a run's would be."""
    shutil.copy(SCOPES, tmp_path / "scopes.xplane.pb")
    monkeypatch.setattr(program_spans, "trace_dir", lambda run: tmp_path)
    windows = [("r0-r3", "decode", i, 0.01 * i, 0.01 * i + 0.004)
               for i in range(STEPS)]
    host = {"windows": windows, "batch": 4, "prompt_len": PROMPT,
            "gen_len": STEPS + 1}
    return harness.Run(cell=tiny_cell(), seed=1, setup_s=1.0, window_s=0.05,
                       attempted=4, failed=0, host=host, checks=[],
                       trace=trace.reduce_trace(SCOPES), device={"count": 1},
                       peaks=harness.peaks_for("TPU v5 lite"))


def test_scopes_are_read_from_the_trace_metadata():
    ops = op_scopes.read(SCOPES)["/device:TPU:0"]
    red = trace.reduce_trace(SCOPES)
    decode = {op_scopes.program_id(n) for n in red.devices[0].prog_names
              if trace.program_name(n) == "decode_step"}
    assert len(decode) == 1
    scopes = {part for (pid, _), tf in ops.items() if pid in decode
              for part in tf.split("/")}
    assert {"mla_q", "mla_absorb", "mla_scores", "mla_out", "moe_router",
            "moe_dispatch", "moe_experts", "moe_combine",
            "moe_shared"} <= scopes
    # each scoped operation is one the trace's events name
    scoped = {op for (pid, op), tf in ops.items() if pid in decode
              and ("/mla_" in tf or "/moe_" in tf)}
    assert scoped and scoped <= set(red.devices[0].op_names)


# At these sizes two of the experts' three einsums fuse into operations
# whose root lies under another scope, so the reader counts one of them
# and the share passes 100%; at the cell's size each is an operation of
# its own (87% in the traced run: PERF.md §5).  The latent-attention
# share is small because a tiny step is bound by latency, not bytes.
READINGS = {
    "mla_decode_roofline": 0.6974758488525197,
    "moe_expert_roofline": 433.3393040974268,
    "expert_load_max_over_mean": 8.0,
}


@pytest.mark.parametrize("name", sorted(READINGS))
def test_new_reader_is_pinned(run, name):
    got = harness.metric_reader(name)(run)
    assert got == pytest.approx(READINGS[name], rel=1e-12, abs=0)
