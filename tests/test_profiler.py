"""End-to-end measurement runtime: dispatch -> monitor -> attribution ->
profiles + traces (paper §4.1-§4.6, Fig. 2)."""
import glob
import os
import threading

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.cct import PLACEHOLDER
from repro.core.profiler import Profiler
from repro.core.profmt import read_profile
from repro.core.sampling import instruction_counts, pc_samples
from repro.core.structure import parse_hlo
from repro.core.trace import read_trace


@pytest.fixture(scope="module")
def compiled():
    def f(x):
        return jnp.tanh(x @ x.T).sum()
    x = jnp.ones((64, 64))
    return jax.jit(f).lower(x).compile(), x


def test_dispatch_attribution(tmp_path, compiled):
    comp, x = compiled
    prof = Profiler(str(tmp_path), tracing=True, rng_seed=0)
    mid = prof.register_module("f", comp.as_text())
    with prof:
        for _ in range(3):
            with prof.dispatch("kernel", "f", stream=0, module_id=mid):
                jax.block_until_ready(comp(x))
        with prof.dispatch("copy", "h2d", stream=1, nbytes=4096):
            pass
    paths = prof.write()
    p = read_profile(paths["cpu_0"])
    inv = p.metrics.index("gpu_kernel/invocations")
    total_inv = sum(v for m, v in zip(p.value_mids, p.values) if m == inv)
    assert total_inv == 3
    cp = p.metrics.index("gpu_copy/bytes")
    assert sum(v for m, v in zip(p.value_mids, p.values) if m == cp) == 4096
    # fine-grained samples attributed under the placeholder
    kinds = [f.kind for f in p.frames]
    assert "gpu_op" in kinds, "PC-sample analogue nodes must exist"
    # placeholder present with stream id
    ph = [f for f in p.frames if f.kind == PLACEHOLDER]
    assert any(f.name == "kernel:f" for f in ph)


def test_per_stream_profiles_and_traces(tmp_path, compiled):
    comp, x = compiled
    prof = Profiler(str(tmp_path), tracing=True, rng_seed=0)
    mid = prof.register_module("f", comp.as_text())
    with prof:
        for s in (0, 1, 2):
            with prof.dispatch("kernel", "f", stream=s, module_id=mid):
                jax.block_until_ready(comp(x))
    paths = prof.write()
    for s in (0, 1, 2):
        assert f"gpu_{s}" in paths
        td = read_trace(paths[f"gpu_trace_{s}"])
        assert len(td.starts) == 1
        assert td.identity["stream"] == s


def test_multithreaded_dispatch(tmp_path, compiled):
    """The Fig. 2 topology: N app threads, one monitor, SPSC only."""
    comp, x = compiled
    prof = Profiler(str(tmp_path), tracing=False, rng_seed=0, unwind=False)
    mid = prof.register_module("f", comp.as_text())
    N, K = 4, 8
    # all workers stay alive until each has dispatched: a thread that
    # exits early can hand its ident to a later one, merging two
    # threads' profiles into one
    done = threading.Barrier(N)

    def worker(i):
        for _ in range(K):
            with prof.dispatch("kernel", "f", stream=i, module_id=mid):
                jax.block_until_ready(comp(x))
        done.wait(timeout=60)

    with prof:
        ts = [threading.Thread(target=worker, args=(i,)) for i in range(N)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        assert prof.flush(timeout=30)
    paths = prof.write()
    cpu_paths = [v for k, v in paths.items()
                 if k.startswith("cpu_") and "trace" not in k]
    assert len(cpu_paths) == N
    total = 0
    for p in cpu_paths:
        d = read_profile(p)
        inv = d.metrics.index("gpu_kernel/invocations")
        total += sum(v for m, v in zip(d.value_mids, d.values) if m == inv)
    assert total == N * K, "every dispatch must be attributed exactly once"
    assert prof._monitor.stats["routed"] == prof._monitor.stats["activities"]


def test_pc_samples_proportional(compiled):
    comp, _ = compiled
    mod = parse_hlo(comp.as_text())
    samples = pc_samples(mod, duration_s=1e-3, rate_hz=1e6)
    assert samples, "1k expected samples"
    total = sum(s.count for s in samples)
    assert total == pytest.approx(1000, rel=0.05)
    ops = mod.all_ops()
    # the dot should dominate the samples for a matmul-heavy kernel
    top = max(samples, key=lambda s: s.count)
    assert ops[top.op_index].opcode in ("dot", "fusion")
    # deterministic without rng
    s2 = pc_samples(mod, duration_s=1e-3, rate_hz=1e6)
    assert [(s.op_index, s.count) for s in samples] == \
        [(s.op_index, s.count) for s in s2]


def test_instruction_counts_loop_multiplier():
    import jax
    def f(x):
        def body(c, _):
            return c @ c, None
        y, _ = jax.lax.scan(body, x, None, length=6)
        return y
    comp = jax.jit(f).lower(jnp.ones((16, 16))).compile()
    mod = parse_hlo(comp.as_text())
    whiles = [op for op in mod.all_ops() if op.opcode == "while"]
    counts = instruction_counts(mod, {whiles[0].name: 6})
    ops = mod.all_ops()
    body_dots = [s for s in counts
                 if ops[s.op_index].opcode == "dot"]
    assert body_dots and body_dots[0].count == 6


def test_flush_quiesces(tmp_path, compiled):
    comp, x = compiled
    prof = Profiler(str(tmp_path), tracing=True, rng_seed=0)
    mid = prof.register_module("f", comp.as_text())
    prof.start()
    with prof.dispatch("kernel", "f", stream=0, module_id=mid):
        jax.block_until_ready(comp(x))
    assert prof.flush(timeout=20)
    prof.stop()


def test_flush_raises_when_monitor_dies(tmp_path):
    """A monitor that dies mid-run must not leave a silently partial
    profile behind: flush and write both raise, chained to the cause."""
    prof = Profiler(str(tmp_path), tracing=True, unwind=False)

    def broken(tid, payloads, lane):
        raise ValueError("attribution failed")
    prof._monitor._handler = broken
    prof.start()
    with prof.dispatch("kernel", "f", stream=0, duration_ns=1000):
        pass
    with pytest.raises(RuntimeError) as err:
        prof.flush(timeout=30)
    assert isinstance(err.value.__cause__, ValueError)
    with pytest.raises(RuntimeError):
        prof.write()
    prof.stop()


def test_flush_raises_when_records_do_not_drain(tmp_path):
    prof = Profiler(str(tmp_path), tracing=True, unwind=False)
    # never started: no monitor drains the ring
    with prof.dispatch("kernel", "f", stream=0, duration_ns=1000):
        pass
    with pytest.raises(TimeoutError):
        prof.flush(timeout=0.05)


def test_database_build_runs_with_the_collector_paused(tmp_path, compiled,
                                                       monkeypatch):
    """flush(), write() and aggregate() run with the cyclic collector
    paused, so that no full pass over the heap falls inside a build, and
    leave it enabled after, also when they raise."""
    import gc
    import importlib
    from repro.core import profiler as profiler_mod
    from repro.core.pipeline import driver
    aggregate = importlib.import_module("repro.core.aggregate").aggregate
    seen = {}

    def spy(name, fn):
        def inner(*a, **k):
            seen[name] = gc.isenabled()
            return fn(*a, **k)
        return inner
    monkeypatch.setattr(Profiler, "_graft_shadow",
                        spy("flush", Profiler._graft_shadow))
    monkeypatch.setattr(profiler_mod, "write_profile",
                        spy("write", profiler_mod.write_profile))
    monkeypatch.setattr(driver, "run", spy("aggregate", driver.run))
    comp, x = compiled
    prof = Profiler(str(tmp_path / "m"), tracing=True, rng_seed=0)
    mid = prof.register_module("f", comp.as_text())
    prof.start()
    with prof.dispatch("kernel", "f", stream=0, module_id=mid):
        jax.block_until_ready(comp(x))
    assert gc.isenabled()
    prof.flush()
    paths = prof.write()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1)
    prof.stop()
    assert seen == {"flush": False, "write": False, "aggregate": False}
    assert gc.isenabled()
    with pytest.raises(FileNotFoundError):
        aggregate([str(tmp_path / "missing.rpro")], str(tmp_path / "db2"))
    assert gc.isenabled()


def test_collector_pause_leaves_the_collector_as_found():
    import gc
    from repro.core.gcpause import collector_paused
    with collector_paused():
        with collector_paused():
            assert not gc.isenabled()
        assert not gc.isenabled()
    assert gc.isenabled()
    gc.disable()
    try:
        with collector_paused():
            pass
        assert not gc.isenabled()
    finally:
        gc.enable()
