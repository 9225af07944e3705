"""Find the benchmark's data by name: cells, configurations, traffic mixes,
metric readers, kernel cost models and the table of peaks.

Everything that belongs to one configuration, traffic mix, metric or
kernel sits in a file of its own; a new one is a new file plus an entry in
BENCHMARK.json, never an edit here.
"""
from __future__ import annotations

import importlib.util
import json
from pathlib import Path

BENCH = Path(__file__).resolve().parents[1]     # <checkout>/bench
ROOT = BENCH.parent                             # <checkout>


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = ROOT) -> dict:
    return load_json(root / "BENCHMARK.json")


def workload(bm: dict, name: str) -> dict:
    for w in bm["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json")


def config(bm: dict, name: str, root: Path = ROOT) -> dict:
    for c in bm["configs"]:
        if c["name"] == name:
            return load_json(root / c["file"])
    raise KeyError(f"no configuration {name!r} in BENCHMARK.json")


def traffic(name: str, bench: Path = BENCH) -> dict:
    return load_json(bench / "traffic" / f"{name}.json")


def peaks(device_kind: str, bench: Path = BENCH) -> dict:
    table = load_json(bench / "peaks.json")["devices"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} is not in "
                       f"bench/peaks.json ({sorted(table)})")
    return table[device_kind]


def _load_module(path: Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        f"{prefix}_{path.stem.replace('-', '_').replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def metric_reader(name: str, bench: Path = BENCH):
    """bench/metrics/<name>.py: UNIT and read(ctx) -> float | None."""
    path = bench / "metrics" / f"{name}.py"
    if not path.exists():
        raise KeyError(f"no reader bench/metrics/{name}.py")
    return _load_module(path, "bench_metric")


def kernel_models(bench: Path = BENCH) -> dict:
    """Every bench/kernels/<kernel>.py, by file name: PATTERN (regex over
    device op names and their HLO metadata) and calls(model, step)."""
    return {p.stem: _load_module(p, "bench_kernel")
            for p in sorted((bench / "kernels").glob("*.py"))}


def cell_metrics(bm: dict, cell: str, trace: bool) -> list[dict]:
    """The metrics a run of `cell` reports: its end-to-end metrics with
    trace off, its per-layer metrics with trace on."""
    e2e = [m for m in bm["end_to_end"] if cell in m.get("workloads", [cell])]
    if not trace:
        return e2e
    reported = {m["name"] for m in e2e}
    return [m for m in bm["per_layer"]
            if (cell in m["workloads"] if "workloads" in m
                else m["moves"] in reported)]
