#!/usr/bin/env python3
"""Run one cell of the chip benchmark and print its result line.

    python3 chipbench/run.py --workload <name> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell, its configuration and its traffic mix are found by name in
``BENCHMARK.json`` at the root of the checkout.  The run warms up (set-up,
``setup_s``), measures for ``--seconds``, builds the database, and then
checks what the timed path produced against the plain reference.  With
``--trace 0`` it reports the cell's end-to-end metrics, with ``--trace 1``
its per-layer metrics, read from a ``jax.profiler`` trace of the window.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, ``checks``); each
number compared is also printed beside its limit as the last lines of
standard error.  Without an accelerator, or with fewer chips than the
cell asks for, it exits with code 2 and prints no result.

The limits of the comparison are set from many seeds, read in one
process, since set-up is long:

    python3 chipbench/run.py --workload <name> --seconds <s> \
        --seeds 1 2 3 [--control] [--fault <name>]

Each seed runs the cell's timed path once for ``--seconds`` (long enough
to finish the mix's longest requests) and prints one JSON line: the
program's readings and whether they are correct; with ``--control``, the
same for the control (the reference in a lower precision in the
program's place); with ``--fault``, the timed path runs with that fault
of ``faults.py`` planted underneath.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    seeds = ap.add_mutually_exclusive_group(required=True)
    seeds.add_argument("--seed", type=int)
    seeds.add_argument("--seeds", type=int, nargs="+")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", action="store_true")
    ap.add_argument("--fault")
    args = ap.parse_args(argv)

    for p in (ROOT, os.path.join(ROOT, "src")):
        if p not in sys.path:
            sys.path.insert(0, p)
    from chipbench import faults, harness

    cell = harness.load_cell(args.workload, ROOT)
    harness.enable_compile_cache(ROOT)
    try:
        harness.device_info(cell.chips)
    except harness.NoAccelerator as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    entry = harness.entry_module(cell)
    out_dir = os.path.join(ROOT, ".chipbench", cell.name)
    with faults.planted(args.fault):
        if args.seeds:
            return calibrate(harness, cell, entry, args, out_dir)
        run = entry.run(cell, seed=args.seed, seconds=args.seconds,
                        trace=bool(args.trace), t_start=T_START,
                        out_dir=out_dir)
    print(f"chipbench: set-up {run.setup_s:.3f} s, window {run.window_s:.3f}"
          f" s, {run.host['window_compiles']} compiles in the window",
          file=sys.stderr)
    run.peaks = harness.peaks_for(run.device["kind"], cell.base)
    metrics = harness.read_metrics(
        run, cell.per_layer if args.trace else cell.end_to_end)
    harness.print_result(harness.result_line(run, metrics), run.checks)
    return 0


def calibrate(harness, cell, entry, args, out_dir) -> int:
    """One line of readings per seed of ``--seeds``, all in this process."""
    import gc
    import json
    for seed in args.seeds:
        t = time.perf_counter()
        run = entry.run(cell, seed=seed, seconds=args.seconds, trace=False,
                        t_start=t, out_dir=out_dir)
        line = {"seed": seed, "fault": args.fault,
                "correct": harness.all_ok(run.checks),
                "checks": {c.name: c.value for c in run.checks},
                "program": run.host["readings"]}
        if args.control and run.host["readings"]:
            control = cell.reference.control_readings(
                cell.config, run.host["program_seed"], run.host["checked"])
            line["control"] = control
            line["control_correct"] = harness.all_ok(
                harness.limit_checks(cell, control))
        line["seconds"] = time.perf_counter() - t
        print(json.dumps(line), flush=True)
        del run
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
