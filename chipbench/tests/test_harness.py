"""The harness on the CPU: a run without a chip prints nothing, a new
configuration, traffic mix and metric are taken from new files alone,
and a whole serving run at a tiny size gives a complete result line."""
import hashlib
import json
import os
import shutil
import subprocess
import sys
import time

from chipbench import harness
from chipbench.entries import serve

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)
    return env


def test_without_an_accelerator_it_exits_nonzero_and_prints_nothing():
    r = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload",
         "qwen2-serve-decode", "--seed", str(2 ** 33 + 5), "--seconds", "1",
         "--trace", "0"], cwd=ROOT, env=_env(), capture_output=True,
        text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""
    assert "no accelerator" in r.stderr


def test_without_the_program_it_exits_nonzero_and_prints_nothing(tmp_path):
    shutil.copy(os.path.join(ROOT, "BENCHMARK.json"), tmp_path)
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    r = subprocess.run(
        [sys.executable, "chipbench/run.py", "--workload", "qwen2-serve-decode",
         "--seed", "3", "--seconds", "1", "--trace", "0"], cwd=tmp_path,
        env=_env(), capture_output=True, text=True, timeout=300)
    assert r.returncode != 0
    assert r.stdout.strip() == ""


def _digest(root):
    out = {}
    for d, _, files in os.walk(root):
        for f in files:
            p = os.path.join(d, f)
            with open(p, "rb") as fh:
                out[os.path.relpath(p, root)] = hashlib.sha256(
                    fh.read()).hexdigest()
    return out


def test_new_config_traffic_and_metric_need_only_new_files(tmp_path):
    shutil.copytree(os.path.join(ROOT, "chipbench"), tmp_path / "chipbench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    base = tmp_path / "chipbench"
    before = _digest(base)

    # a throwaway configuration: its file of sizes and its reference
    conf = harness.load_json(base / "configs" / "qwen2-1.5b.json")
    conf["program"].update(n_layers=2, d_model=64, n_heads=4, n_kv_heads=2,
                           d_ff=128, vocab=256, head_dim=16)
    (base / "configs" / "throwaway-dense.json").write_text(json.dumps(conf))
    shutil.copy(base / "configs" / "qwen2-1.5b.py",
                base / "configs" / "throwaway-dense.py")
    # a throwaway traffic mix: data only
    (base / "traffic" / "throwaway-mix.json").write_text(json.dumps(
        {"entry": "serve", "batch": 2, "prompt_len": 8, "gen_len": 4,
         "sample_requests": 2}))
    # a throwaway metric: a reader of its own
    (base / "metrics" / "throwaway_dispatches.py").write_text(
        "def read(run):\n    return run.host.get('dispatches')\n")
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    bench["configs"].append({"name": "throwaway-dense", "source": "test",
                             "file": "chipbench/configs/throwaway-dense.json",
                             "reduced": [], "why": "test"})
    bench["workloads"].append({"name": "throwaway", "config":
                               "throwaway-dense", "traffic": "throwaway-mix",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "throwaway_dispatches", "unit":
                               "dispatches", "better": "higher", "source":
                               "program_counter", "layer": "test",
                               "moves": "serve_tokens_per_s",
                               "workloads": ["throwaway"]})
    for m in bench["end_to_end"]:
        if "qwen2-serve-decode" in m.get("workloads", ()):
            m["workloads"].append("throwaway")
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))

    cell = harness.load_cell("throwaway", str(tmp_path))
    assert cell.base == str(base)
    assert [m["name"] for m in cell.per_layer] == ["throwaway_dispatches"]
    assert {m["name"] for m in cell.end_to_end} == {
        "serve_tokens_per_s", "token_gap_p95_ms",
        "db_build_us_per_dispatch", "setup_s"}
    t = time.perf_counter()
    run = harness.entry_module(cell).run(
        cell, seed=7, seconds=1.0, trace=False, t_start=t,
        out_dir=str(tmp_path / "out"), allow_cpu=True)
    got = harness.read_metrics(run, cell.per_layer + cell.end_to_end)
    assert got["throwaway_dispatches"]["value"] == run.host["dispatches"] > 0
    assert got["throwaway_dispatches"]["unit"] == "dispatches"
    assert set(got) >= {"serve_tokens_per_s", "setup_s"}
    after = _digest(base)
    assert {k: v for k, v in after.items() if k in before} == before


def test_result_line_of_a_tiny_serving_run(tmp_path):
    from chipbench.tests import tiny
    cell = tiny.serve_cell()
    cell.end_to_end = [{"name": n, "unit": u} for n, u in (
        ("serve_tokens_per_s", "tokens/s"), ("setup_s", "s"),
        ("db_build_us_per_dispatch", "us/dispatch"))]
    run = serve.run(cell, seed=2 ** 33 + 11, seconds=1.5, trace=False,
                    t_start=time.perf_counter(), out_dir=str(tmp_path),
                    allow_cpu=True)
    run.peaks = harness.peaks_for("TPU v5 lite")
    line = harness.result_line(run, harness.read_metrics(
        run, cell.end_to_end))
    assert line["correct"] is True
    assert line["attempted"] > 0 and line["failed"] == 0
    assert list(line)[-1] == "checks"
    assert line["device"]["platform"] == "cpu"
    assert line["device"]["count"] == 1 and line["device"]["kind"]
    assert set(line["metrics"]) == {"serve_tokens_per_s", "setup_s",
                                    "db_build_us_per_dispatch"}
    assert run.host["window_compiles"] == 0
    assert line["checks"]["db_windows_mismatched"]["value"] == 0
