"""ISSUE 7: the always-on serving profiler.

Pins the tentpole contracts: per-request/per-phase window identities are
ordinary host frames and stay byte-deterministic through ``aggregate()``
and ``merge_databases``; the overhead governor's control law (step-down,
patience-gated step-up, backpressure shed, floor clamp) and its
convergence under real dispatch load; telemetry snapshots round-trip
through the fleet daemon exactly once (duplicate redelivery dedups,
re-export conflicts quarantine); and backpressure flows daemon ->
transport -> producer -> governor over both transports.
"""
import os
import shutil
import time

import numpy as np
import pytest

from repro.core.aggregate import aggregate
from repro.core.merge import merge_databases
from repro.fleet.client import (DirectoryTransport, ShardProducer,
                                SocketTransport, TransportError)
from repro.fleet.daemon import FleetDaemon, SocketIngest
from repro.serving.governor import (GovernorConfig, LEVELS,
                                    OverheadGovernor)
from repro.serving.live import ServingProfiler
from repro.serving.stats import ServingStats
from repro.serving.telemetry import (SERVING_METRICS, TelemetryExporter,
                                     read_telemetry)
from repro.serving.window import (DECODE, PREFILL, WINDOW_MODULE,
                                  request_frames, window_label)
from repro.traceview.stats import (request_attribution,
                                   request_latency_percentiles,
                                   window_labels)
from repro.traceview.tracedb import TraceDB

from test_merge import assert_db_identical, db_bytes

FLOOR = len(LEVELS) - 1


def _spin(ns):
    end = time.perf_counter_ns() + ns
    while time.perf_counter_ns() < end:
        pass


def serve_run(out_dir, n_requests=3, gen_len=2, rid_prefix="r", **kw):
    """A small synthetic serving run; returns (profile paths, traces)."""
    sp = ServingProfiler(str(out_dir), **kw)
    with sp:
        for i in range(n_requests):
            with sp.request(f"{rid_prefix}{i}", PREFILL, tokens=8):
                with sp.profiler.dispatch("kernel", "prefill", stream=0):
                    _spin(200_000)
            for _ in range(gen_len):
                with sp.request(f"{rid_prefix}{i}", DECODE, tokens=1):
                    with sp.profiler.dispatch("kernel", "decode",
                                              stream=0):
                        _spin(100_000)
        sp.profiler.flush()
        paths = sp.write()
    # pair each profile with its trace via the write() key scheme
    # (cpu_N <-> cpu_trace_N, gpu_S <-> gpu_trace_S)
    pairs = []
    for k in sorted(paths):
        if "trace" in k:
            continue
        fam, idx = k.rsplit("_", 1)
        pairs.append((paths[k], paths.get(f"{fam}_trace_{idx}")))
    profs = [p for p, _ in pairs]
    traces = [t for _, t in pairs if t]
    return sp, profs, traces, dict(pairs)


# ---------------------------------------------------------------------------
# Window identities
# ---------------------------------------------------------------------------
def test_window_frames_roundtrip():
    req, ph = request_frames("r7", DECODE)
    assert req.module == ph.module == WINDOW_MODULE
    assert window_label(req) == ("r7", None)
    assert window_label(ph) == (None, DECODE)
    (only,) = request_frames("r7")
    assert window_label(only) == ("r7", None)
    # non-window frames decode to (None, None)
    from repro.core.cct import Frame, HOST
    assert window_label(Frame(HOST, "request:r7", "app.py", 0)) == \
        (None, None)


def test_windows_survive_aggregation(tmp_path):
    _, profs, traces, _ = serve_run(tmp_path / "run", governor=False)
    db = aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1,
                   trace_paths=traces)
    window_frames = [f for f in db.frames if f.module == WINDOW_MODULE]
    names = {f.name for f in window_frames}
    assert {"request:r0", "request:r1", "request:r2",
            "phase:prefill", "phase:decode"} <= names
    req, ph = window_labels(db)
    assert {r for r in req if r} == {"r0", "r1", "r2"}
    assert {p for p in ph if p} == {PREFILL, DECODE}
    # a phase ctx always sits inside its request window
    assert all(r is not None for r, p in zip(req, ph) if p is not None)


def test_windows_byte_deterministic_through_merge(tmp_path):
    """The tentpole invariant: request windows are ordinary frames, so
    the canonical-database contract holds unchanged — a one-shot
    aggregate of a windowed run is byte-identical to a sharded
    aggregate + merge of the same profiles."""
    # two serving "hosts" (ranks): the fleet's real sharding unit — a
    # gpu trace maps its contexts through its own rank's host profile,
    # so a shard always carries a rank's full profile family
    runs = [serve_run(tmp_path / f"run{r}", n_requests=3, rank=r,
                      rid_prefix=f"h{r}-r", governor=False)
            for r in range(2)]
    profs = [p for _, ps, _, _ in runs for p in ps]
    traces = [t for _, _, ts, _ in runs for t in ts]
    one = str(tmp_path / "one")
    aggregate(profs, one, trace_paths=traces)
    shards = []
    for i, (_, ps, ts, _) in enumerate(runs):
        d = str(tmp_path / f"shard{i}")
        aggregate(ps, d, trace_paths=ts)
        shards.append(d)
    merged = str(tmp_path / "merged")
    merge_databases(shards, merged)
    assert_db_identical(merged, one)
    # and shard order is irrelevant, windows or not
    again = str(tmp_path / "again")
    merge_databases(list(reversed(shards)), again)
    assert db_bytes(again) == db_bytes(merged)


def test_request_attribution_from_database(tmp_path):
    _, profs, traces, _ = serve_run(tmp_path / "run", n_requests=3,
                                    gen_len=2, governor=False)
    db = aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1,
                   trace_paths=traces)
    lines = TraceDB(db.trace_db_path()).line_views()
    rows = request_attribution(lines, db)
    assert {r[0] for r in rows} == {"r0", "r1", "r2"}
    for _, total, phases in rows:
        assert total > 0
        assert phases.get(PREFILL, 0) > 0 and phases.get(DECODE, 0) > 0
    pct = request_latency_percentiles(lines, db)
    # spans cover the whole phase: prefill >= its 200us spin, the decode
    # phase >= its gen_len x 100us spins
    assert pct[PREFILL][50.0] >= 0.2
    assert pct[DECODE][50.0] >= 0.2
    assert pct[PREFILL][99.0] >= pct[PREFILL][50.0]


# ---------------------------------------------------------------------------
# Governor control law (scripted stub profiler: pure feedback logic)
# ---------------------------------------------------------------------------
class StubProfiler:
    def __init__(self):
        self.sample_scale = None
        self.sample_cap = None
        self.unwind_depth = None
        self.c = {"dispatches": 0, "tool_ns": 0, "app_ns": 0}

    def overhead_counters(self):
        return dict(self.c)

    def window(self, n, frac):
        """Advance n dispatches at the given tool/app overhead."""
        self.c["dispatches"] += n
        self.c["app_ns"] += n * 1_000_000
        self.c["tool_ns"] += int(n * 1_000_000 * frac)


def make_gov(**cfg):
    prof = StubProfiler()
    gov = OverheadGovernor(prof, GovernorConfig(
        budget=0.10, headroom=0.5, interval=4, patience=2, **cfg))
    return prof, gov


def test_governor_applies_knobs_on_init():
    prof, gov = make_gov()
    lv = LEVELS[0]
    assert (prof.sample_scale, prof.sample_cap, prof.unwind_depth) == \
        (lv.sample_scale, lv.sample_cap, lv.unwind_depth)


def test_governor_steps_down_when_over_budget():
    prof, gov = make_gov()
    prof.window(4, 0.5)                  # way over 0.10
    d = gov.observe()
    assert d is not None and d.level == 1 and gov.throttle_downs == 1
    lv = LEVELS[1]
    assert (prof.sample_scale, prof.sample_cap, prof.unwind_depth) == \
        (lv.sample_scale, lv.sample_cap, lv.unwind_depth)


def test_governor_no_decision_before_interval():
    prof, gov = make_gov()
    prof.window(3, 0.5)                  # < interval dispatches
    assert gov.observe() is None and gov.level == 0


def test_governor_clamps_at_floor():
    prof, gov = make_gov()
    for _ in range(FLOOR + 3):           # more over-budget windows than rungs
        prof.window(4, 0.9)
        gov.observe()
    assert gov.level == FLOOR
    assert gov.throttle_downs == FLOOR   # clamped steps don't count
    assert LEVELS[FLOOR].sample_scale == 0.0   # floor still measures: the
    assert LEVELS[FLOOR].sample_cap == 1       # never-off contract


def test_governor_patience_gates_step_up():
    prof, gov = make_gov()
    prof.window(4, 0.5)
    gov.observe()                        # down to 1
    prof.window(4, 0.01)                 # low window #1: no step yet
    gov.observe()
    assert gov.level == 1
    prof.window(4, 0.01)                 # low window #2 == patience
    gov.observe()
    assert gov.level == 0 and gov.throttle_ups == 1


def test_governor_midband_resets_streak():
    prof, gov = make_gov()
    prof.window(4, 0.5)
    gov.observe()                        # down to 1
    prof.window(4, 0.01)                 # low #1
    gov.observe()
    prof.window(4, 0.08)                 # in (headroom*budget, budget]: hold
    gov.observe()
    prof.window(4, 0.01)                 # low #1 again — streak was reset
    gov.observe()
    assert gov.level == 1


def test_governor_backpressure_sheds_and_blocks_step_up():
    prof, gov = make_gov()
    gov.note_backpressure(True)          # shed one level on transition
    assert gov.level == 1 and gov.throttle_downs == 1
    gov.note_backpressure(True)          # steady state: no further shed
    assert gov.level == 1
    for _ in range(4):                   # low windows can't raise fidelity
        prof.window(4, 0.01)
        gov.observe()
    assert gov.level == 1
    gov.note_backpressure(False)         # released: patience applies again
    for _ in range(2):
        prof.window(4, 0.01)
        gov.observe()
    assert gov.level == 0


def test_governor_state_surface():
    prof, gov = make_gov()
    prof.window(4, 0.5)
    gov.observe()
    st = gov.state()
    assert st["level"] == 1 and st["level_name"] == LEVELS[1].name
    assert st["decisions"] == 1 and st["overhead"] == pytest.approx(0.5)
    assert st["budget"] == pytest.approx(0.10)


def test_governor_converges_under_real_load(tmp_path):
    """Against a real profiler and an unreachable budget the controller
    must walk the whole ladder to the floor; with a generous budget it
    must hold full fidelity."""
    sp = ServingProfiler(str(tmp_path / "tight"),
                         governor=GovernorConfig(budget=0.001, interval=4),
                         sample_rate_hz=1e6)
    with sp:
        for i in range(12 * len(LEVELS)):
            with sp.request(f"r{i}", DECODE, tokens=1):
                with sp.profiler.dispatch("kernel", "step", stream=0):
                    _spin(50_000)
    assert sp.governor.level == FLOOR
    assert sp.governor.throttle_downs >= FLOOR
    # generous: dispatch cost against 2ms spins sits far below 500%.
    # The SLO shed is off here (it has tests of its own): it reacts to
    # the wall-clock p99 of the spins, which a loaded host stretches
    sp2 = ServingProfiler(str(tmp_path / "loose"),
                          governor=GovernorConfig(
                              budget=5.0, interval=4,
                              slo_degradation=float("inf")),
                          sample_rate_hz=1e6)
    with sp2:
        for i in range(16):
            with sp2.request(f"r{i}", DECODE, tokens=1):
                with sp2.profiler.dispatch("kernel", "step", stream=0):
                    _spin(2_000_000)
    assert sp2.governor.level == 0 and sp2.governor.throttle_downs == 0
    assert sp2.governor.slo_sheds == 0


# ---------------------------------------------------------------------------
# SLO shed (ISSUE 10 satellite): p99 degradation beats the budget check
# ---------------------------------------------------------------------------
def test_governor_slo_sheds_under_budget():
    """Windows are comfortably under budget, but the serving p99 blows
    past the rolling baseline: the governor must shed anyway, keep
    shedding while degraded, never let the incident poison the
    baseline, and refuse to raise fidelity until the p99 recovers."""
    prof, gov = make_gov()
    for _ in range(3):                   # healthy windows seed the EMA
        prof.window(4, 0.01)
        gov.observe(p99_ms=10.0)
    assert gov.level == 0 and gov.slo_baseline_ms == pytest.approx(10.0)
    prof.window(4, 0.01)                 # under budget, p99 3x baseline
    gov.observe(p99_ms=30.0)
    assert gov.level == 1 and gov.slo_sheds == 1 and gov.slo_degraded
    prof.window(4, 0.01)                 # still degraded: keeps shedding
    gov.observe(p99_ms=30.0)
    assert gov.level == 2 and gov.slo_sheds == 2
    assert gov.slo_baseline_ms == pytest.approx(10.0)   # unpoisoned
    st = gov.state()
    assert st["slo_degraded"] and st["slo_sheds"] == 2
    assert st["slo_baseline_ms"] == pytest.approx(10.0)


def test_governor_slo_recovery_restores_step_up():
    prof, gov = make_gov()
    for _ in range(2):
        prof.window(4, 0.01)
        gov.observe(p99_ms=10.0)
    prof.window(4, 0.01)
    gov.observe(p99_ms=40.0)             # shed to 1
    # degraded blocks step-up even through low windows with no p99
    # signal (the verdict stands until a healthy p99 clears it)
    for _ in range(3):
        prof.window(4, 0.01)
        gov.observe()
    assert gov.level == min(1 + 3, FLOOR)         # kept shedding, never rose
    level_during_incident = gov.level
    # recovery: healthy p99 clears the flag; patience applies as usual
    prof.window(4, 0.01)
    gov.observe(p99_ms=10.0)
    assert not gov.slo_degraded and gov.level == level_during_incident
    prof.window(4, 0.01)
    gov.observe(p99_ms=10.0)             # low streak == patience: step up
    assert gov.level == level_during_incident - 1


def test_governor_slo_converges_to_floor_under_persistent_degradation():
    """Convergence: a p99 that stays degraded regardless of fidelity
    walks the ladder to the floor and holds there — it never oscillates
    back up and never steps below the floor."""
    prof, gov = make_gov()
    prof.window(4, 0.01)
    gov.observe(p99_ms=10.0)             # baseline
    levels = []
    for _ in range(3 * len(LEVELS)):
        prof.window(4, 0.01)
        gov.observe(p99_ms=100.0)
        levels.append(gov.level)
    assert gov.level == FLOOR
    assert levels == sorted(levels)      # monotone walk down, no hunting
    assert gov.slo_baseline_ms == pytest.approx(10.0)
    # identical hysteresis: the budget path's counters are untouched
    assert gov.throttle_ups == 0


def test_governor_slo_baseline_tracks_slow_drift():
    """A gradual p99 drift inside the degradation band is the new
    normal: the EMA follows it and no shed fires."""
    prof, gov = make_gov()
    p99 = 10.0
    for _ in range(10):
        prof.window(4, 0.2)              # over budget: sheds on budget
        gov.observe(p99_ms=p99)
        p99 *= 1.1                       # EMA lag keeps p99/baseline < 1.5
    assert gov.slo_sheds == 0
    assert gov.slo_baseline_ms > 10.0


def test_governor_p99_none_is_pure_budget_control():
    """No latency signal ever: behavior is the pre-SLO control law."""
    prof, gov = make_gov()
    prof.window(4, 0.5)
    gov.observe()
    assert gov.level == 1 and gov.slo_sheds == 0
    assert gov.slo_baseline_ms is None
    for _ in range(2):
        prof.window(4, 0.01)
        gov.observe()
    assert gov.level == 0


def test_governor_config_validates_slo_knobs():
    with pytest.raises(ValueError):
        GovernorConfig(slo_degradation=0.0)
    with pytest.raises(ValueError):
        GovernorConfig(slo_alpha=0.0)
    with pytest.raises(ValueError):
        GovernorConfig(slo_alpha=1.5)


# ---------------------------------------------------------------------------
# ServingStats
# ---------------------------------------------------------------------------
def test_serving_stats_rolling_window():
    t = [0.0]
    st = ServingStats(window_s=10.0, clock=lambda: t[0])
    for i in range(4):
        st.record(f"r{i}", PREFILL, 4_000_000, tokens=8)
        st.record(f"r{i}", DECODE, 1_000_000, tokens=1)
        t[0] += 1.0
    assert st.requests_in_window() == 4
    assert st.percentile_ms(PREFILL, 50) == pytest.approx(4.0)
    assert st.percentile_ms(DECODE, 50) == pytest.approx(1.0)
    assert st.tok_s() == pytest.approx(36 / 3.0)
    t[0] += 100.0                        # everything ages out
    assert st.requests_in_window() == 0
    assert st.percentile_ms(PREFILL, 50) == 0.0


def test_serving_stats_snapshot_matches_telemetry_columns():
    st = ServingStats()
    st.record("r0", PREFILL, 2_000_000, tokens=4)
    snap = st.snapshot()
    assert set(SERVING_METRICS) <= set(snap)
    assert all(isinstance(v, float) for v in snap.values())


# ---------------------------------------------------------------------------
# Telemetry round trip: exactly-once through the fleet daemon
# ---------------------------------------------------------------------------
def fleet_fixture(tmp_path, **producer_kw):
    daemon = FleetDaemon(str(tmp_path / "fleet"), str(tmp_path / "spool"))
    producer = ShardProducer(str(tmp_path / "outbox"),
                             DirectoryTransport(daemon.incoming_dir),
                             producer="hostA", sleep=lambda s: None,
                             **producer_kw)
    return daemon, producer


def snap_for(epoch):
    return {"requests": 2.0, "tokens": 16.0, "tok_s": 100.0 + epoch,
            "decode_p50_ms": 1.5, "governor_level": 2.0}


def test_telemetry_roundtrips_exactly_once(tmp_path):
    daemon, producer = fleet_fixture(tmp_path)
    exporter = TelemetryExporter(producer, host="hostA", rank=0)
    for e in range(3):
        exporter.export(snap_for(e))
    r = daemon.poll_once()
    assert len(r.applied) == 3 and not r.quarantined
    rows = read_telemetry(daemon.database())
    assert [row["epoch"] for row in rows] == [0.0, 1.0, 2.0]
    assert [row["tok_s"] for row in rows] == [100.0, 101.0, 102.0]
    assert rows[0]["host"] == "hostA"
    # unset columns surface as 0.0, not missing
    assert rows[0]["prefill_p99_ms"] == 0.0


def test_telemetry_duplicate_redelivery_dedups(tmp_path):
    daemon, producer = fleet_fixture(tmp_path)
    exporter = TelemetryExporter(producer, host="hostA", rank=0,
                                 deliver=False)
    exporter.export(snap_for(0))
    (env,) = producer.spooled()
    dup = str(tmp_path / "dup.shard")
    shutil.copy(env, dup)
    producer.deliver()
    daemon.poll_once()
    # the crash-redelivery path: the exact same envelope arrives again
    shutil.copy(dup, os.path.join(daemon.incoming_dir,
                                  os.path.basename(env)))
    r = daemon.poll_once()
    assert r.duplicates and not r.applied and not r.quarantined
    assert len(read_telemetry(daemon.database())) == 1


def test_telemetry_reexported_epoch_quarantines(tmp_path):
    """Same (host, rank, epoch), different payload: the deterministic
    shard id turns a double-export into a visible journal conflict, and
    the folded series keeps the first value."""
    daemon, producer = fleet_fixture(tmp_path)
    exporter = TelemetryExporter(producer, host="hostA", rank=0)
    exporter.export(snap_for(0))
    daemon.poll_once()
    exporter.export({"tok_s": 999.0}, epoch=0)      # re-export epoch 0
    r = daemon.poll_once()
    assert len(r.quarantined) == 1
    assert "different payload" in r.quarantined[0][1]
    rows = read_telemetry(daemon.database())
    assert len(rows) == 1 and rows[0]["tok_s"] == 100.0


def test_telemetry_shard_id_is_deterministic():
    exporter = TelemetryExporter(object(), host="node-3.rack/7", rank=2)
    sid = exporter.shard_id(5)
    assert sid == exporter.shard_id(5)
    assert "/" not in sid and sid.endswith("-r2-e00000005")


# ---------------------------------------------------------------------------
# Backpressure: daemon -> transport -> producer -> governor
# ---------------------------------------------------------------------------
def test_directory_backpressure_follows_daemon_spool(tmp_path):
    daemon, producer = fleet_fixture(tmp_path, daemon_spool_soft=2)
    exporter = TelemetryExporter(producer, host="hostA", rank=0)
    for e in range(4):                   # delivered but not yet folded
        exporter.export(snap_for(e))
    assert producer.poll_backpressure() is True
    assert producer.daemon_spool_depth == 4
    gov = OverheadGovernor(StubProfiler(), GovernorConfig(budget=0.1))
    gov.note_backpressure(producer.throttled)
    assert gov.level == 1                # shed on transition
    daemon.poll_once()                   # daemon drains its spool
    assert producer.poll_backpressure() is False
    assert daemon.spool_depth() == 0


def test_socket_backpressure_poll(tmp_path):
    daemon, _ = fleet_fixture(tmp_path)
    sock = str(tmp_path / "fleet.sock")
    listener = SocketIngest(daemon, sock)
    listener.start()
    try:
        transport = SocketTransport(sock)
        producer = ShardProducer(str(tmp_path / "outbox2"), transport,
                                 producer="hostB", daemon_spool_soft=1,
                                 sleep=lambda s: None)
        exporter = TelemetryExporter(producer, host="hostB", rank=1)
        for e in range(3):
            exporter.export(snap_for(e))
        assert transport.poll_status()["spool_depth"] == 3
        assert producer.poll_backpressure() is True
        daemon.poll_once()
        assert producer.poll_backpressure() is False
    finally:
        listener.stop()


def test_stage_outbox_fill_sheds_daemonless(tmp_path):
    """Regression (ISSUE 10 satellite): a producer that only *stages* —
    no deliver loop, no daemon, no explicit poll — must still see its
    own outbox filling, so the governor sheds before the exporter keeps
    writing full-fidelity measurements into a pipe nothing drains."""
    class DeadTransport:                 # no poll_status, send never works
        def send(self, path):
            raise TransportError("daemon is gone")

    src = tmp_path / "db"
    src.mkdir()
    (src / "meta.json").write_text("{}")
    producer = ShardProducer(str(tmp_path / "outbox"), DeadTransport(),
                             spool_soft=2, sleep=lambda s: None)
    gov = OverheadGovernor(StubProfiler(), GovernorConfig(budget=0.10))
    for e in range(4):
        (src / "payload.bin").write_bytes(b"x%d" % e)   # distinct shards
        producer.stage(str(src), epoch=e)
        gov.note_backpressure(producer.throttled)
    assert producer.throttled            # 4 spooled > soft bound 2
    assert gov.level == 1 and gov.throttle_downs == 1


def test_stage_polls_daemon_backpressure(tmp_path):
    """The bugfix proper: ``stage()`` must call ``poll_backpressure``
    (not just the local bound check), so a stage-only producer observes
    the *daemon's* backlog the moment it enqueues."""
    class CountingTransport:
        def __init__(self):
            self.polls = 0

        def send(self, path):
            raise TransportError("unused")

        def poll_status(self):
            self.polls += 1
            return {"spool_depth": 7}

    src = tmp_path / "db"
    src.mkdir()
    (src / "meta.json").write_text("{}")
    transport = CountingTransport()
    producer = ShardProducer(str(tmp_path / "outbox"), transport,
                             spool_soft=32, daemon_spool_soft=3,
                             sleep=lambda s: None)
    producer.stage(str(src), epoch=0)
    assert transport.polls == 1          # polled on the enqueue itself
    assert producer.daemon_backpressured and producer.throttled
    assert producer.daemon_spool_depth == 7


# ---------------------------------------------------------------------------
# ServingProfiler integration: status + periodic export
# ---------------------------------------------------------------------------
def test_serving_profiler_status_and_periodic_export(tmp_path):
    daemon, producer = fleet_fixture(tmp_path)
    sp = ServingProfiler(str(tmp_path / "run"), producer=producer,
                         export_every_s=0.0, governor=True)
    with sp:
        for i in range(3):
            with sp.request(f"r{i}", PREFILL, tokens=4):
                with sp.profiler.dispatch("kernel", "prefill", stream=0):
                    _spin(100_000)
    status = sp.status()
    assert set(SERVING_METRICS) <= set(status)
    assert status["requests"] == 3.0
    assert status["epochs_exported"] >= 3.0
    assert status["prefill_p50_ms"] > 0
    daemon.poll_once()
    rows = read_telemetry(daemon.database())
    assert len(rows) == int(status["epochs_exported"])
    assert [row["epoch"] for row in rows] == \
        sorted(row["epoch"] for row in rows)


# ---------------------------------------------------------------------------
# Overlapping windows (continuous batching): per-dispatch stamping
# ---------------------------------------------------------------------------
def test_overlapping_windows_attribute_exactly_once(tmp_path):
    """Regression (ISSUE 8): a continuous-batching scheduler holds many
    requests' windows open at once and interleaves their decode steps on
    one thread.  The whole-extent ``with`` splice would stack both
    windows (every dispatch lands in both requests — double counted);
    per-dispatch ``step()`` stamping must attribute each dispatch to
    exactly one request, so ``request_attribution`` sums exactly to the
    partition's total GPU busy ns."""
    from repro.core.profiler import Profiler
    from repro.serving.window import RequestWindow

    prof = Profiler(str(tmp_path / "run"), tracing=True, unwind=False)
    w1 = RequestWindow(prof, "r1", DECODE)
    w2 = RequestWindow(prof, "r2", DECODE)
    with prof:
        w1.open()
        with w1.step(PREFILL):           # r1 prefills alone
            with prof.dispatch("kernel", "prefill", stream=0):
                _spin(200_000)
        w2.open()                        # r2 joins the batch mid-flight
        for _ in range(3):               # interleaved decode steps
            with w1.step():
                with prof.dispatch("kernel", "decode", stream=0):
                    _spin(100_000)
            with w2.step():
                with prof.dispatch("kernel", "decode", stream=0):
                    _spin(100_000)
        w1.close()
        w2.close()
        prof.flush()
        paths = prof.write()
    # both spans overlap (that's the point) and each covers its steps
    assert w1.duration_ns > w2.duration_ns > 0
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    db = aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1,
                   trace_paths=traces)
    lines = TraceDB(db.trace_db_path()).line_views()
    gpu = [td for td in lines if td.identity.get("type") == "gpu"]
    total_gpu_ns = sum(float((td.ends - td.starts).sum()) for td in gpu)
    assert total_gpu_ns > 0
    rows = request_attribution(lines, db)
    assert {r[0] for r in rows} == {"r1", "r2"}
    by_rid = {r[0]: r for r in rows}
    # exactly-once: the per-request split partitions the GPU total
    assert sum(total for _, total, _ in rows) == \
        pytest.approx(total_gpu_ns, rel=1e-9)
    # r1 carries the prefill + its decodes; r2 decodes only
    assert by_rid["r1"][2].get(PREFILL, 0) > 0
    assert by_rid["r1"][2].get(DECODE, 0) > 0
    assert set(by_rid["r2"][2]) == {DECODE}
    # decode work is symmetric across the batch (same spins)
    assert by_rid["r1"][2][DECODE] == \
        pytest.approx(by_rid["r2"][2][DECODE], rel=0.5)


def test_window_exclusive_replaces_not_nests(tmp_path):
    """``Profiler.window_exclusive`` swaps the thread's window stack for
    its body and restores it after — dispatches inside a step carry only
    that request's frames even under a live ``with``-style window."""
    from repro.core.profiler import Profiler
    from repro.serving.window import RequestWindow

    prof = Profiler(str(tmp_path / "run"), tracing=True, unwind=False)
    with prof:
        with RequestWindow(prof, "outer", DECODE):
            w = RequestWindow(prof, "inner", DECODE)
            with w.step():
                with prof.dispatch("kernel", "decode", stream=0):
                    _spin(50_000)
            # restored: this dispatch belongs to the outer window again
            with prof.dispatch("kernel", "decode", stream=0):
                _spin(50_000)
        prof.flush()
        paths = prof.write()
    profs = [p for k, p in sorted(paths.items()) if "trace" not in k]
    traces = [p for k, p in sorted(paths.items()) if "trace" in k]
    db = aggregate(profs, str(tmp_path / "db"), n_ranks=1, n_threads=1,
                   trace_paths=traces)
    lines = TraceDB(db.trace_db_path()).line_views()
    rows = {r[0]: r[1] for r in request_attribution(lines, db)}
    assert set(rows) == {"outer", "inner"}
    req, _ = window_labels(db)
    # no context carries both identities (replacement, not nesting)
    assert all(r in (None, "outer", "inner") for r in req)
