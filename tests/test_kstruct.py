"""Kernel-interior attribution (ISSUE 8 tentpole; repro.core.kstruct).

Covers the whole thread: structure recovery from the real Pallas
kernels (jaxpr trace -> loops / inlined scopes / source lines), the
sample descent (two-level draw, governor cap preserved exactly), the
profiler splice (interior frames under the kernel's GPU_OP context),
both ``top_hot_loops`` views, the counter-collector refinement, and the
canonical-database byte contract (one-shot aggregate == shards +
merge_databases with interiors attributed).

Plus the ISSUE 8 sampling satellite: the deterministic ``pc_samples``
path must never return an empty list for a non-empty module, even at
the governor floor (cap=1) over a many-op module with spread weights.
"""
import os

import numpy as np
import pytest

from repro.core import sampling
from repro.core.aggregate import aggregate
from repro.core.cct import Frame, GPU_FUNC, GPU_LOOP, GPU_OP
from repro.core.kstruct import KernelLeaf, KernelStructure
from repro.core.merge import merge_databases
from repro.core.profiler import Profiler
from repro.core.structure import parse_hlo
from test_merge import assert_db_identical


# ---------------------------------------------------------------------------
# fixtures
# ---------------------------------------------------------------------------
KERNEL_HLO = """HloModule kmod

ENTRY %main (p0: f32[256,256]) -> f32[256,256] {
  %p0 = f32[256,256] parameter(0)
  %fa = f32[256,256] custom-call(%p0), custom_call_target="tpu_custom_call", metadata={op_name="jit(step)/flash_attention"}
  %mul = f32[256,256] multiply(%fa, %fa), metadata={op_name="jit(step)/scale"}
  ROOT %out = f32[256,256] add(%mul, %p0)
}
"""


def hand_structure(name="flash_attention", file="flash.py"):
    """A small deterministic interior: one grid loop, two scopes,
    weighted leaves — jax-independent, so goldens/determinism tests do
    not depend on jaxpr spelling across jax versions."""
    loop = Frame(GPU_LOOP, "grid:kv_blocks", file, 36)
    blk = Frame(GPU_FUNC, "_block", file, 63)
    init = Frame(GPU_FUNC, "_init", file, 44)
    return KernelStructure(name, file, 36, [
        KernelLeaf((loop, blk, Frame(GPU_OP, "dot_general", file, 67)),
                   weight=6e-6, stall="compute", flops=2.1e9, bytes=0.0),
        KernelLeaf((loop, blk, Frame(GPU_OP, "exp", file, 80)),
                   weight=1e-6, stall="compute", flops=1.8e8, bytes=0.0),
        KernelLeaf((loop, init, Frame(GPU_OP, "swap", file, 47)),
                   weight=1.5e-6, stall="memory", flops=0.0, bytes=3.3e7),
    ])


def bound_module():
    mod = parse_hlo(KERNEL_HLO)
    assert mod.bind_kernel_structure(hand_structure()) == 1
    return mod


# ---------------------------------------------------------------------------
# sample descent (distribute)
# ---------------------------------------------------------------------------
def test_distribute_exact_total_and_deterministic():
    ks = hand_structure()
    for count in (1, 2, 7, 100, 12345):
        pairs = ks.distribute(count)
        assert sum(c for _, c in pairs) == count    # cap survives exactly
        assert pairs == ks.distribute(count)        # pure function
        assert all(c > 0 for _, c in pairs)
    assert ks.distribute(0) == []


def test_distribute_rng_total_preserved():
    ks = hand_structure()
    rng = np.random.default_rng(3)
    for count in (1, 9, 400):
        assert sum(c for _, c in ks.distribute(count, rng)) == count


def test_distribute_single_sample_goes_to_heaviest_leaf():
    ks = hand_structure()
    [(leaf, c)] = ks.distribute(1)
    assert c == 1
    assert leaf == int(np.argmax([lf.weight for lf in ks.leaves]))


def test_distribute_many_equal_leaves_exact():
    """Largest-remainder apportionment: equal weights, count not a
    multiple of the leaf count — floor+0.5 rounding would overshoot or
    undershoot; apportionment hits the total exactly."""
    file = "k.py"
    leaves = [KernelLeaf((Frame(GPU_OP, f"op{i}", file, i),),
                         weight=1.0, stall="compute") for i in range(7)]
    ks = KernelStructure("k", file, 1, leaves)
    for count in (1, 3, 7, 10, 20):
        assert sum(c for _, c in ks.distribute(count)) == count


# ---------------------------------------------------------------------------
# satellite: deterministic pc_samples never empty (governor floor)
# ---------------------------------------------------------------------------
def test_pc_samples_cap1_never_empty_many_ops():
    """Regression (ISSUE 8): with cap=1 and weights spread over many ops
    (every p < 0.5), np.floor(n*p + 0.5) rounded every count to zero and
    pc_samples returned [] — fine-grained attribution silently died at
    the governor's floor rung."""
    lines = ["HloModule many", "",
             "ENTRY %main (p0: f32[64,64]) -> f32[64,64] {",
             "  %p0 = f32[64,64] parameter(0)"]
    prev = "p0"
    for i in range(40):
        lines.append(f"  %op{i} = f32[64,64] multiply(%{prev}, %p0)")
        prev = f"op{i}"
    lines += [f"  ROOT %out = f32[64,64] add(%{prev}, %p0)", "}"]
    mod = parse_hlo("\n".join(lines))
    w, _ = sampling.op_weights(mod)
    p = w / w.sum()
    assert p.max() < 0.5                       # the failing regime
    samples = sampling.pc_samples(mod, 1.0, rate_hz=1e6, cap=1)
    assert samples, "deterministic pc_samples returned [] at cap=1"
    assert sum(s.count for s in samples) == 1
    # the fallback attributes the sample to the heaviest op
    assert samples[0].op_index == int(np.argmax(w))


def test_pc_samples_cap_respected_with_bound_kernel():
    mod = bound_module()
    for cap in (1, 5, 64):
        samples = sampling.pc_samples(mod, 1.0, rate_hz=1e6, cap=cap)
        assert samples
        assert sum(s.count for s in samples) <= cap


# ---------------------------------------------------------------------------
# binding + two-level draw
# ---------------------------------------------------------------------------
def test_bind_matches_custom_call_by_op_name():
    mod = parse_hlo(KERNEL_HLO)
    assert mod.bind_kernel_structure(hand_structure()) == 1
    (idx, ks), = mod.kernel_structures().items()
    assert mod.all_ops()[idx].opcode == "custom-call"
    assert ks.name == "flash_attention"
    # no match -> no binding
    assert mod.bind_kernel_structure(
        hand_structure(name="nonexistent_kernel")) == 0


def test_bound_custom_call_gains_interior_weight():
    plain = parse_hlo(KERNEL_HLO)
    wp, _ = sampling.op_weights(plain)
    mod = bound_module()
    wb, _ = sampling.op_weights(mod)
    ccall = next(op.index for op in mod.all_ops()
                 if op.opcode == "custom-call")
    # interior roofline model raises the op's modeled time above the
    # opaque custom-call heuristic
    assert wb[ccall] > wp[ccall] > 0.0


def test_two_level_draw_descends_into_leaves():
    mod = bound_module()
    samples = sampling.pc_samples(mod, 1e-3, rate_hz=1e6)
    ccall = next(op.index for op in mod.all_ops()
                 if op.opcode == "custom-call")
    interior = [s for s in samples if s.op_index == ccall]
    assert interior and all(s.leaf >= 0 for s in interior)
    assert {s.leaf for s in interior} <= {0, 1, 2}
    ks = mod.kernel_structures()[ccall]
    for s in interior:
        assert s.stall == ks.leaves[s.leaf].stall
    # non-bound ops stay leafless
    assert all(s.leaf == -1 for s in samples if s.op_index != ccall)


# ---------------------------------------------------------------------------
# recovery from the real Pallas kernels
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def recovered():
    pytest.importorskip("jax")     # recovery traces real Pallas kernels
    from repro.kernels import kernel_structures
    return {ks.name: ks for ks in kernel_structures()}


def test_recovers_all_three_kernels(recovered):
    assert set(recovered) == {"flash_attention", "decode_attention",
                              "ssm_scan", "mla_decode"}
    for ks in recovered.values():
        assert len(ks.leaves) >= 10
        assert ks.active_s > 0 and ks.total_flops > 0


def test_flash_attention_interior_shape(recovered):
    ks = recovered["flash_attention"]
    assert ks.file == "flash_attention.py"
    kinds = {f.kind for lf in ks.leaves for f in lf.frames}
    assert kinds == {GPU_LOOP, GPU_FUNC, GPU_OP}
    # the sequential grid axis is the kernel's outer loop
    assert all(lf.frames[0].name == "grid:kv_blocks" for lf in ks.leaves)
    # pl.when bodies appear as inlined scopes with call-site lines
    scopes = {f.name for lf in ks.leaves for f in lf.frames
              if f.kind == GPU_FUNC}
    assert {"_init", "_block", "_finish"} <= scopes
    # the MXU matmuls are recovered as compute-bound dot_general leaves
    dots = [lf for lf in ks.leaves if lf.frames[-1].name == "dot_general"]
    assert len(dots) >= 2
    assert all(lf.stall == "compute" and lf.flops > 0 for lf in dots)
    # scratch init traffic is memory-bound
    init = [lf for lf in ks.leaves
            if any(f.name == "_init" for f in lf.frames)]
    assert init and all(lf.stall == "memory" for lf in init)
    # leaves carry real source lines of the kernel file
    assert all(lf.line > 0 for lf in ks.leaves)


def test_decode_and_ssm_interiors(recovered):
    dec = recovered["decode_attention"]
    assert all(lf.frames[0].name == "grid:kv_blocks" for lf in dec.leaves)
    ssm = recovered["ssm_scan"]
    assert all(lf.frames[0].name == "grid:chunks" for lf in ssm.leaves)
    # ssd kernel: three MXU matmuls per chunk
    dots = [lf for lf in ssm.leaves
            if lf.frames[-1].name == "dot_general"]
    assert len(dots) >= 3


def test_mla_decode_interior(recovered):
    """The latent-attention decode kernel: the slot blocks are its
    sequential loop; a block's body holds its MXU products (the scores
    against the latent and roped keys, one line; the probabilities
    against the latent, another), and the new token's column starts the
    softmax in ``_init``."""
    ks = recovered["mla_decode"]
    assert ks.file == "mla_decode.py"
    assert all(lf.frames[0].name == "grid:slot_blocks" for lf in ks.leaves)
    dots = [lf for lf in ks.leaves if lf.frames[-1].name == "dot_general"
            and any(f.name == "_block" for f in lf.frames)]
    assert len(dots) >= 2
    assert all(lf.stall == "compute" and lf.flops > 0 for lf in dots)
    scopes = {f.name for lf in ks.leaves for f in lf.frames
              if f.kind == GPU_FUNC}
    assert {"_init", "_block", "_finish"} <= scopes


def test_source_info_failure_propagates(monkeypatch, recovered):
    """A failing source-info lookup raises instead of recovering a
    kernel of one leaf with no file — the failure that once hid behind
    an ``except Exception: return []``."""
    from jax._src import source_info_util
    from repro.kernels import flash_attention

    def boom(traceback):
        raise AttributeError("source info unavailable")
    monkeypatch.setattr(source_info_util, "user_frames", boom)
    with pytest.raises(AttributeError, match="source info unavailable"):
        flash_attention.kernel_structure()


def test_recovery_is_deterministic(recovered):
    from repro.kernels import flash_attention
    a = flash_attention.kernel_structure()
    b = flash_attention.kernel_structure()
    assert [lf.frames for lf in a.leaves] == [lf.frames for lf in b.leaves]
    assert [lf.weight for lf in a.leaves] == [lf.weight for lf in b.leaves]


# ---------------------------------------------------------------------------
# profiler splice + views
# ---------------------------------------------------------------------------
def run_rank(out_dir, rank=0):
    prof = Profiler(str(out_dir), tracing=True, unwind=False, rank=rank)
    mid = prof.register_module("step", KERNEL_HLO)
    prof.register_kernel_structures(mid, [hand_structure()])
    with prof:
        for _ in range(4):
            with prof.dispatch("kernel", "step", stream=0, module_id=mid,
                               duration_ns=1_000_000):
                pass
        prof.flush()
        paths = prof.write()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    return profs, traces


def test_interior_frames_under_kernel_op(tmp_path):
    profs, traces = run_rank(tmp_path / "m0")
    db = aggregate(profs, str(tmp_path / "db"), trace_paths=traces)
    roots = [g for g, f in enumerate(db.frames)
             if f.kind == GPU_FUNC and db.parents[g] >= 0
             and db.frames[int(db.parents[g])].kind == GPU_OP]
    assert roots, "no kernel-interior root (GPU_FUNC under GPU_OP)"
    assert {db.frames[g].name for g in roots} == {"flash_attention"}
    # interior leaves carry gpu_inst samples
    samp = db.stats["sum"][:, db.metric_id("gpu_inst/samples")]
    assert samp[roots[0]] > 0        # inclusive: the whole descent
    names = {db.frames[g].name for g in range(len(db.frames))}
    assert {"grid:kv_blocks", "_block", "dot_general"} <= names


def test_viewer_top_hot_loops(tmp_path):
    from repro.core import viewer
    profs, traces = run_rank(tmp_path / "m0")
    db = aggregate(profs, str(tmp_path / "db"), trace_paths=traces)
    out = viewer.top_hot_loops(db)
    assert "flash_attention" in out
    assert "grid:kv_blocks" in out
    assert "flash.py:67" in out and "dot_general" in out
    # stall breakdown columns are present
    assert "compute" in out and "memory" in out
    # a database without gpu_inst degrades gracefully
    from test_goldens import fixture_db as _  # noqa: F401 (idiom check)
    out2 = viewer.top_hot_loops(db, top=1)
    assert len(out2.splitlines()) == 3       # header + colnames + 1 row


def test_traceview_top_hot_loops_joins_busy_ns(tmp_path):
    from repro.traceview.stats import top_hot_loops
    from repro.traceview.tracedb import TraceDB
    profs, traces = run_rank(tmp_path / "m0")
    db = aggregate(profs, str(tmp_path / "db"), trace_paths=traces)
    lines = TraceDB(db.trace_db_path()).line_views()
    rows = top_hot_loops(lines, db)
    assert rows
    kernels = {r[0] for r in rows}
    assert kernels == {"flash_attention"}
    # sample counts positive and busy estimate prorated from the
    # enclosing placeholder's windowed busy time
    assert all(r[4] > 0 for r in rows)
    assert sum(r[5] for r in rows) > 0
    # rows sorted by samples descending
    assert [r[4] for r in rows] == sorted((r[4] for r in rows),
                                          reverse=True)


def test_interior_byte_determinism_shards_vs_oneshot(tmp_path):
    """ISSUE 8 acceptance: a 2-rank kernel-interior-attributed one-shot
    aggregate() is byte-identical to per-rank shards + merge_databases
    (interior frames are ordinary tree paths; the canonical-database
    contract holds unchanged)."""
    runs = [run_rank(tmp_path / f"m{r}", rank=r) for r in range(2)]
    profs = [p for ps, _ in runs for p in ps]
    traces = [t for _, ts in runs for t in ts]
    one = str(tmp_path / "one")
    aggregate(profs, one, trace_paths=traces)
    shards = []
    for i, (ps, ts) in enumerate(runs):
        d = str(tmp_path / f"shard{i}")
        aggregate(ps, d, trace_paths=ts)
        shards.append(d)
    merged = str(tmp_path / "merged")
    merge_databases(shards, merged)
    assert_db_identical(merged, one)


# ---------------------------------------------------------------------------
# counter-collector refinement
# ---------------------------------------------------------------------------
def test_static_counters_refined_by_bound_structure():
    from repro.counters.collector import static_counters
    from repro.counters.taxonomy import COUNTER_INDEX
    plain = static_counters(parse_hlo(KERNEL_HLO)).copy()
    bound = static_counters(bound_module()).copy()
    i_fl, i_mxu = COUNTER_INDEX["flops"], COUNTER_INDEX["mxu_flops"]
    i_inst = COUNTER_INDEX["inst_executed"]
    ks = hand_structure()
    assert bound[i_fl] == pytest.approx(plain[i_fl] + ks.total_flops)
    assert bound[i_mxu] == pytest.approx(plain[i_mxu] + 2.1e9)
    assert bound[i_inst] == pytest.approx(plain[i_inst] + len(ks.leaves))
    assert bound[COUNTER_INDEX["active_ns"]] > plain[
        COUNTER_INDEX["active_ns"]]


def test_binding_invalidates_module_caches():
    mod = parse_hlo(KERNEL_HLO)
    from repro.counters.collector import static_counters
    w0, _ = sampling.op_weights(mod)
    c0 = static_counters(mod).copy()
    mod.bind_kernel_structure(hand_structure())
    w1, _ = sampling.op_weights(mod)
    c1 = static_counters(mod)
    assert w1.sum() > w0.sum()
    assert c1.sum() > c0.sum()


def test_real_kernels_end_to_end_in_viewer(tmp_path, recovered):
    """ISSUE 8 acceptance: PC samples inside flash_attention,
    decode_attention, and ssm_scan attribute to named interior contexts
    visible in viewer top-down and traceview top_hot_loops."""
    from repro.core import viewer
    from repro.traceview.stats import top_hot_loops
    from repro.traceview.tracedb import TraceDB
    names = ("flash_attention", "decode_attention", "ssm_scan")
    lines_hlo = ["HloModule step", "",
                 "ENTRY %main (p0: f32[256,256]) -> f32[256,256] {",
                 "  %p0 = f32[256,256] parameter(0)"]
    prev = "p0"
    for i, n in enumerate(names):
        lines_hlo.append(
            f'  %k{i} = f32[256,256] custom-call(%{prev}), '
            f'custom_call_target="tpu_custom_call", '
            f'metadata={{op_name="jit(step)/{n}"}}')
        prev = f"k{i}"
    lines_hlo += [f"  ROOT %out = f32[256,256] add(%{prev}, %p0)", "}"]
    prof = Profiler(str(tmp_path / "m"), tracing=True, unwind=False)
    mid = prof.register_module("step", "\n".join(lines_hlo))
    assert prof.register_kernel_structures(
        mid, [recovered[n] for n in names]) == 3
    with prof:
        for _ in range(4):
            with prof.dispatch("kernel", "step", stream=0, module_id=mid,
                               duration_ns=1_000_000):
                pass
        prof.flush()
        paths = prof.write()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    db = aggregate(profs, str(tmp_path / "db"), trace_paths=traces)
    td = viewer.top_down(db, "gpu_inst/samples", max_depth=30)
    for n in names:
        assert n in td, f"{n} interior missing from viewer top-down"
    # GPU_LOOP frames render as "loop at <file>:<line>" in top-down
    assert "loop at flash_attention.py" in td
    assert "loop at ssm_scan.py" in td
    rows = top_hot_loops(TraceDB(db.trace_db_path()).line_views(), db,
                         k=100)
    assert {r[0] for r in rows} == set(names)
    # rows point at real kernel source files and lines
    assert any(r[2].startswith("flash_attention.py:") for r in rows)
