"""The benchmark's general machinery: find a cell's files by name, check
the device, time set-up, read the metrics and print the result line.

Everything that belongs to one configuration, traffic mix or metric is a
file of its own, found by the name ``BENCHMARK.json`` gives it:

- ``configs/<config>.json``  the sizes as they are run, and the limits
                             of the comparison that decides ``correct``;
- ``configs/<config>.py``    the plain reference beside them, with the
                             functions that count operations and bytes;
- ``traffic/<traffic>.json`` which entry drives the job, and its sizes;
- ``entries/<entry>.py``     the code that runs one entry of the program;
- ``metrics/<metric>.py``    a reader, ``read(run) -> float | None``.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import os
import sys
import time
from typing import Any, Callable, Dict, List, Optional

import numpy as np

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


class NoAccelerator(RuntimeError):
    """JAX sees no accelerator, or fewer chips than the cell asks for."""


def load_module(path: str, name: Optional[str] = None):
    """Import one file of the benchmark by its path (names may hold
    ``-`` and ``.``, which ``import`` does not take)."""
    name = name or "chipbench_" + os.path.relpath(path, HERE).replace(
        os.sep, "_").replace("-", "_").replace(".", "_")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    """One workload of ``BENCHMARK.json`` with the files it names."""
    name: str
    chips: int
    config_name: str
    config: dict                 # configs/<config>.json
    reference: Any               # configs/<config>.py
    traffic_name: str
    traffic: dict                # traffic/<traffic>.json
    end_to_end: List[dict]       # metric entries this cell reports
    per_layer: List[dict]
    base: str = HERE             # the benchmark's directory


def _reports(metric: dict, cell: str) -> bool:
    return cell in metric.get("workloads", (cell,))


def load_cell(name: str, root: str = ROOT) -> Cell:
    bench = load_json(os.path.join(root, "BENCHMARK.json"))
    base = os.path.join(root, bench["paths"][0])
    work = {w["name"]: w for w in bench["workloads"]}
    if name not in work:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(known: {sorted(work)})")
    w = work[name]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    config_file = os.path.join(root, conf["file"])
    e2e = [m for m in bench["end_to_end"] if _reports(m, name)]
    per_layer = [m for m in bench["per_layer"] if _reports(m, name)]
    return Cell(
        name=name, chips=int(w["chips"]), config_name=w["config"],
        config=load_json(config_file),
        reference=load_module(os.path.splitext(config_file)[0] + ".py"),
        traffic_name=w["traffic"],
        traffic=load_json(os.path.join(base, "traffic",
                                       w["traffic"] + ".json")),
        end_to_end=e2e, per_layer=per_layer, base=base)


def entry_module(cell: Cell):
    return load_module(os.path.join(cell.base, "entries",
                                    cell.traffic["entry"] + ".py"))


def metric_reader(name: str, base: str = HERE) -> Callable:
    return load_module(os.path.join(base, "metrics", name + ".py")).read


def program_config(config: dict):
    """The program's ``ModelConfig`` for a configuration file: its
    registry entry with every size the file's ``program`` group gives."""
    from repro.configs import get_config
    sizes = dict(config["program"])
    base = get_config(sizes.pop("registry"))
    for k, v in sizes.items():
        if isinstance(v, list):
            sizes[k] = tuple(v)
    return dataclasses.replace(base, **sizes)


def program_seed(seed: int) -> int:
    """The seed handed to the program's entry: a non-negative 31-bit
    number drawn from ``--seed``, which may exceed 32 bits."""
    return int(np.random.SeedSequence(seed).generate_state(1)[0]
               & 0x7FFFFFFF)


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------
def device_info(chips: int, allow_cpu: bool = False) -> dict:
    import jax
    devs = jax.devices()
    dev = devs[0]
    if dev.platform == "cpu" and not allow_cpu:
        raise NoAccelerator("JAX found no accelerator (platform cpu)")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX sees "
                            f"{len(devs)}")
    return {"platform": dev.platform, "kind": dev.device_kind,
            "count": chips}


def memory_peak_bytes(chips: int) -> Optional[int]:
    import jax
    peaks = []
    for dev in jax.devices()[:chips]:
        stats = dev.memory_stats() or {}
        if "peak_bytes_in_use" in stats:
            peaks.append(int(stats["peak_bytes_in_use"]))
    return max(peaks) if peaks else None


def peaks_for(kind: str, base: str = HERE) -> dict:
    """The chip's peak rates from ``peaks.json``; a kind the table lacks
    is an error, never a default."""
    kinds = load_json(os.path.join(base, "peaks.json"))["kinds"]
    if kind not in kinds:
        raise KeyError(f"no peak rates for device kind {kind!r} "
                       f"(known: {sorted(kinds)})")
    return kinds[kind]


def enable_compile_cache(root: str = ROOT) -> str:
    """JAX's persistent compilation cache at a fixed path inside the
    checkout (gitignored), so only a cell's first run there compiles."""
    path = os.path.join(root, ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    import jax
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


# ---------------------------------------------------------------------------
# what an entry hands back, and the metric readers' view of it
# ---------------------------------------------------------------------------
@dataclasses.dataclass
class Check:
    """One number compared with its limit: ``ok`` when value <= limit
    (or == for an exact count)."""
    name: str
    value: float
    limit: float
    exact: bool = False

    @property
    def ok(self) -> bool:
        if not np.isfinite(self.value):
            return False
        return self.value == self.limit if self.exact \
            else self.value <= self.limit


def all_ok(checks: List[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def all_ok(checks: List[Check]) -> bool:
    return bool(checks) and all(c.ok for c in checks)


def limit_checks(cell: Cell, readings: Dict[str, float]) -> List[Check]:
    """A check for each reading the configuration file sets a limit
    for; the reference may read more numbers than are compared."""
    limits = cell.config["limits"]
    return [Check(name, float(readings[name]), float(limit))
            for name, limit in limits.items()]


@dataclasses.dataclass
class Run:
    """Everything one run measured; metric readers take what they need
    and return None where it holds nothing for them."""
    cell: Cell
    seed: int
    setup_s: float
    window_s: float                     # host clock, measured window
    attempted: int
    failed: int
    host: Dict[str, Any]                # entry-specific host readings
    checks: List[Check]
    trace: Optional[Any] = None         # trace.Reduction of a traced run
    device: Optional[dict] = None
    peaks: Optional[dict] = None


def read_metrics(run: Run, entries: List[dict]) -> Dict[str, dict]:
    """Each metric's reader, found by its name; a reader that finds
    nothing to read returns None and the metric is left out."""
    out = {}
    for m in entries:
        value = metric_reader(m["name"], run.cell.base)(run)
        if value is None:
            continue
        out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out


def result_line(run: Run, metrics: Dict[str, dict]) -> dict:
    device = dict(run.device or {})
    line = {"correct": all_ok(run.checks),
            "attempted": int(run.attempted), "failed": int(run.failed),
            "metrics": metrics, "device": device}
    if run.trace is not None:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        line["breakdown"] = run.trace.breakdown()
    # a reading that is not a number (NaN) goes out as null, so that the
    # line stays JSON; it fails its check either way
    line["checks"] = {c.name: {"value": c.value if np.isfinite(c.value)
                               else None, "limit": c.limit}
                      for c in run.checks}
    return line


def print_result(line: dict, checks: List[Check]) -> None:
    for c in checks:
        rel = "==" if c.exact else "<="
        print(f"check {c.name}: {c.value!r} (limit {rel} {c.limit!r}) "
              f"{'ok' if c.ok else 'FAILED'}", file=sys.stderr, flush=True)
    print(json.dumps(line), flush=True)


# the host clock every window and set-up time is read from
now = time.perf_counter
