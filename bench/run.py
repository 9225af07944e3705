#!/usr/bin/env python3
"""Run one benchmark cell once and print its result line.

    python3 bench/run.py --workload internlm2-longgen --seed 7 \
        --seconds 20 --trace 0

The cell (configuration × traffic mix) is looked up by name in
BENCHMARK.json; its configuration file, traffic file and metric readers
are found under bench/ by name. With --trace 0 the result carries the
cell's end-to-end metrics; with --trace 1 its per-layer metrics, read from
a device trace of a few steps in the middle of the window and from the
benchmark's host-clock spans. The last line of standard output is one JSON
object; the numbers compared with the reference, each beside its limit,
are the last lines of standard error and the last key of that object.
`memory_peak_bytes` is the most the device held during the window (bytes
in use after each step's call); the key `memory` adds the set-up's peak,
the KV pool and the most of it the lanes held.

Exits 3, printing no result, when JAX finds no TPU or fewer chips than the
cell asks for. JAX's compilation cache lives in .bench/jax_cache inside
the checkout, so only a cell's first run there compiles.
"""
from __future__ import annotations

import time

STARTED = time.perf_counter()

import os  # noqa: E402

# bfloat16 stays bfloat16 between operations: the configuration states it,
# and the reference rounds where it says (before JAX starts its backend,
# which reads XLA_FLAGS once)
os.environ["XLA_FLAGS"] = " ".join(filter(None, (
    os.environ.get("XLA_FLAGS"), "--xla_allow_excess_precision=false")))

import argparse  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness, spec  # noqa: E402


def result_line(bm: dict, cell: str, res: dict, trace: bool) -> dict:
    ctx = res["ctx"]
    metrics = {}
    for m in spec.cell_metrics(bm, cell, trace):
        value = spec.metric_reader(m["name"]).read(ctx)
        if value is not None:
            metrics[m["name"]] = {"value": float(value), "unit": m["unit"]}
    device = dict(res["device"], memory_peak_bytes=res["memory_peak_bytes"])
    line = {"correct": res["correct"], "attempted": res["attempted"],
            "failed": res["failed"], "metrics": metrics, "device": device}
    if trace:
        t = ctx.trace
        device["busy_s"] = t["busy_s"]
        device["window_s"] = t["window_s"]
        line["breakdown"] = {"device_ops": t["device_ops"],
                             "idle_gaps": t["idle_gaps"]}
    line["memory"] = res["memory"]
    line["compared"] = res["compared"]
    return line


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--control", type=int, choices=(0, 1), default=0,
                    help="put the control in the program's place: the tokens "
                         "compared are those that the reference with float8 "
                         "keys and values puts first at each served position, "
                         "so the run reads not correct (not part of a "
                         "benchmark run)")
    args = ap.parse_args(argv)

    bm = spec.benchmark()
    cell = spec.workload(bm, args.workload)
    conf = spec.config(bm, cell["config"])
    mix = spec.traffic(cell["traffic"])
    out_dir = spec.ROOT / ".bench"
    shutil.rmtree(out_dir / "trace", ignore_errors=True)
    try:
        res = harness.run_cell(conf, mix, seed=args.seed,
                               seconds=args.seconds, trace=bool(args.trace),
                               started=STARTED, chips=cell["chips"],
                               cache_dir=out_dir / "jax_cache",
                               out_dir=out_dir, control=bool(args.control))
    except harness.NoChip as e:
        print(f"no result: {e}", file=sys.stderr)
        return 3
    line = result_line(bm, args.workload, res, bool(args.trace))
    print(f"reference: {res['reference_s']:.3f} s, grid scale "
          f"{res['grid'][0]} zero point {res['grid'][1]}, "
          f"{res['tokens_not_first']} served tokens not its first choice",
          file=sys.stderr)
    mem = res["memory"]
    print("memory: " + ", ".join(f"{k} {v}" for k, v in mem.items()),
          file=sys.stderr)
    if args.control:
        print("control: the tokens compared are the float8-KV reference's "
              "first choices", file=sys.stderr)
    for name, c in line["compared"].items():
        print(f"compared {name} = {c['value']!r} (limit {c['limit']!r})",
              file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
