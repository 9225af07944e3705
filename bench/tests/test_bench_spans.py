"""The readers of the program's own spans, scopes and counters
(benchlib/spans.py): on small made-up traces, on a whole traced run on the
CPU at a test size, and on a program that has none of them."""
import json
import re
import sys
import time
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
sys.path.insert(0, str(Path(__file__).resolve().parent))

from benchlib import harness, spans, spec, trace  # noqa: E402

PHASES = ("plan", "fetch", "sample", "admit")
NEW = [f"{p}_ms_per_step" for p in PHASES] + [
    "kv_pool_copy_ms_per_step", "setup_compile_s", "window_compiles"]


def read(name, ctx):
    return spec.metric_reader(name).read(ctx)


def small():
    """Window 0–1000 ns with two steps; ops of one device: pool slices of
    the layer scan, a slice inside `attn`, the loop, a kernel."""
    scan = "jit(paged_step)/layer_scan/while/body"
    dev = [["%fusion.1 = dynamic-slice", 100, 160, ""],
           ["%fusion.2 = dynamic-update-slice", 300, 340, ""],
           ["%fusion.3 = dynamic-slice", 400, 420, ""],
           ["%while.2 = while", 90, 800, ""],
           ["%paged_attn.1 = custom-call", 500, 600, ""],
           ["%fusion.1 = dynamic-slice", 1100, 1200, ""]]
    host = [[trace.WINDOW_SPAN, 0, 1000],
            ["bench.step", 0, 480], ["bench.step", 500, 990],
            ["serve.plan", 10, 40], ["serve.device", 40, 400],
            ["serve.fetch", 400, 430], ["serve.sample", 430, 460],
            ["serve.admit", 460, 470],
            ["serve.plan", 510, 550], ["serve.device", 550, 900],
            ["serve.fetch", 900, 950], ["serve.sample", 950, 970],
            ["serve.admit", 970, 980],
            ["serve.plan", 1010, 1090]]          # after the window
    op_names = {"fusion.1": {f"{scan}/squeeze"},
                "fusion.2": {f"{scan}/dynamic_update_slice"},
                "fusion.3": {f"{scan}/attn/dynamic_slice"},
                "while.2": {"jit(paged_step)/layer_scan/while"},
                "paged_attn.1": {f"{scan}/attn/pallas_call"}}
    return {"device": {"/device:TPU:0": dev}, "host": host,
            "op_names": op_names}


def test_phase_means_over_the_window():
    t = small()
    assert spans.phase_ms_per_step(t, "plan", 2) == pytest.approx(
        1e-6 * (30 + 40) / 2)
    assert spans.phase_ms_per_step(t, "fetch", 2) == pytest.approx(
        1e-6 * 80 / 2)
    assert spans.phase_ms_per_step(t, "nothing", 2) is None


def test_pool_copy_scope_leaves_attention_out():
    t = small()
    # 100–160 and 300–340 count; the slice inside attn, the while loop,
    # the kernel and the op after the window do not
    assert spans.scope_ms_per_step(t, spans.POOL_COPY, 2) == pytest.approx(
        1e-6 * 100 / 2)
    assert spans.scope_ms_per_step(t, re.compile("no such scope"), 2) \
        is None


def test_op_names_from_a_profiler_trace(tmp_path):
    """The HLO the profiler keeps in its trace gives each instruction's
    scope."""
    import glob

    import jax
    import jax.numpy as jnp

    def layer(c, y):
        with jax.named_scope("mlp"):
            return c + jnp.sin(y), None

    @jax.jit
    def step(x):
        with jax.named_scope("layer_scan"):
            return jax.lax.scan(layer, x[0], x)[0]

    step(jnp.ones((4, 8))).block_until_ready()
    with jax.profiler.trace(str(tmp_path)):
        step(jnp.ones((4, 8))).block_until_ready()
    [path] = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    with open(path, "rb") as f:
        names = spans.op_names(f.read())
    ops = {o for v in names.values() for o in v}
    assert "jit(step)/layer_scan/while/body/dynamic_slice" in ops
    assert any(o.startswith("jit(step)/layer_scan/while/body/closed_call/"
                            "mlp/") for o in ops)
    scan = {o for o in ops if spans.POOL_COPY.search(o)}
    assert "jit(step)/layer_scan/while/body/dynamic_slice" in scan
    assert not any("/mlp/" in o for o in scan)


def test_readers_without_a_trace_or_counter(monkeypatch):
    ctx = harness.Ctx(trace=None, traced_steps=[], t_w0=0.0, t_w1=1.0)
    for name in NEW[:5]:
        assert read(name, ctx) is None
    # a program without the compile counter reads nothing either
    from repro.runtime import telemetry
    monkeypatch.setattr(telemetry, "KERNEL_COUNTERS", object())
    assert read("setup_compile_s", ctx) is None
    assert read("window_compiles", ctx) is None


def test_compile_readers_split_at_the_window():
    from repro.runtime.telemetry import KERNEL_COUNTERS
    KERNEL_COUNTERS.reset()
    KERNEL_COUNTERS.compile_log.extend([(1.0, "compile", 2.5),
                                        (2.0, "cache_load", 0.5),
                                        (5.0, "compile", 1.0)])
    ctx = harness.Ctx(t_w0=3.0, t_w1=6.0)
    assert read("setup_compile_s", ctx) == pytest.approx(3.0)
    assert read("window_compiles", ctx) == 1
    KERNEL_COUNTERS.reset()


@pytest.fixture(scope="module")
def traced_cpu_run(tmp_path_factory):
    """One traced run of the CPU test cell (bench/tests/cpu_cell.py's
    sizes), with the readers pointed at its trace."""
    import cpu_cell
    out = tmp_path_factory.mktemp("bench")
    conf, mix = cpu_cell.small("internlm2-1.8b", "longgen")
    res = harness.run_cell(conf, mix, seed=2**33 + 11, seconds=2.0,
                           trace=True, started=time.perf_counter(),
                           require_chip=False, out_dir=out)
    return res["ctx"], out / "trace"


def test_new_readers_in_a_traced_cpu_run(traced_cpu_run, monkeypatch):
    ctx, trace_dir = traced_cpu_run
    monkeypatch.setattr(spans, "TRACE_DIR", trace_dir)
    got = {name: read(name, ctx) for name in NEW}
    for name in NEW:
        if name == "kv_pool_copy_ms_per_step":
            continue                      # no device ops on the CPU
        assert got[name] is not None and np.isfinite(got[name]), name
    assert got["window_compiles"] == 0
    assert got["setup_compile_s"] > 0
    assert all(got[f"{p}_ms_per_step"] > 0 for p in PHASES)
    # the four host phases fit inside the steps' host time, which also
    # holds the benchmark's own bookkeeping around each device call
    host = read("host_ms_per_step", ctx)
    assert sum(got[f"{p}_ms_per_step"] for p in PHASES) < 1.5 * host


DATA = Path(__file__).resolve().parents[1] / "testdata"
KERNELS = {k: v.PATTERN for k, v in spec.kernel_models().items()}


def recorded(name):
    with open(DATA / name) as f:
        rec = json.load(f)
    t = rec["trace"]
    t["op_names"] = {k: set(v) for k, v in t.get("op_names", {}).items()}
    lo, hi = trace.window_of(t)
    steps = sum(1 for n, s, e in t["host"]
                if n == "bench.step" and lo <= s and e <= hi)
    return t, rec["reduced"], steps


def innermost_span(host, mid):
    """The shortest bench.* or serve.* host span open at `mid`."""
    best, width = None, None
    for n, s, e in host:
        if n == trace.WINDOW_SPAN or not n.startswith(("bench.", "serve.")) \
                or not s <= mid <= e:
            continue
        if width is None or e - s < width:
            best, width = n, e - s
    return best


def test_recorded_spans_trace_reduces_as_recorded():
    """Three steps of a chip trace with the program's names, spans and
    scopes (bench/testdata/recorded_trace_spans.json)."""
    t, want, steps = recorded("recorded_trace_spans.json")
    r = trace.reduce(t, KERNELS)
    assert steps == 3
    for k in ("window_s", "busy_s"):
        assert r[k] == pytest.approx(want[k])
    assert r["kernel_s"] == pytest.approx(want["kernel_s"])
    # the kernels by their pallas_call names, matched by the same patterns
    names = [n for n, _ in r["device_ops"]]
    assert "cim_mvm" in names and "paged_attn" in names
    assert "kv_write" in names and "kv_write" not in r["kernel_s"]


def test_recorded_kernel_calls_per_step_unchanged():
    """The same kernel calls per step as the trace recorded before the
    kernels were named (bench/testdata/recorded_trace.json)."""
    new, _, n_new = recorded("recorded_trace_spans.json")
    old, _, n_old = recorded("recorded_trace.json")
    per_new = {k: v / n_new
               for k, v in trace.reduce(new, KERNELS)["kernel_calls"].items()}
    per_old = {k: v / n_old
               for k, v in trace.reduce(old, KERNELS)["kernel_calls"].items()}
    assert per_new == per_old == {"cim_mvm": 169.0, "paged_attn": 24.0}


def test_recorded_idle_gaps_fall_in_serve_phases():
    """Every idle gap of the device longer than a millisecond (the host's
    work between steps) lies inside one of the program's host phases
    (serve.*), not only in the benchmark's step; the rest are sub-µs
    gaps inside a device call."""
    t, _, _ = recorded("recorded_trace_spans.json")
    lo, hi = trace.window_of(t)
    busy = trace._union([(max(s, lo), min(e, hi))
                         for ops in t["device"].values()
                         for n, s, e, _ in ops
                         if min(e, hi) > max(s, lo) and trace.op_name(n)
                         .split(".")[0] not in trace.CONTROL_FLOW])
    edges = [lo] + [x for iv in busy for x in iv] + [hi]
    gaps = sorted(((b - a, (a + b) // 2) for a, b in
                   zip(edges[0::2], edges[1::2]) if b > a), reverse=True)
    long = [innermost_span(t["host"], mid) for g, mid in gaps if g > 1e6]
    assert len(long) >= 3 and all(x.startswith("serve.") for x in long), \
        long
    assert max(g for g, _ in gaps[len(long):]) < 1e3      # ns


def test_recorded_pool_copies_found_by_scope():
    """The layer scan's pool slices, found by named scope, take the same
    device time as the fusions XLA names for them."""
    t, want, steps = recorded("recorded_trace_spans.json")
    kv = spans.scope_ms_per_step(t, spans.POOL_COPY, steps)
    assert kv == pytest.approx(want["kv_pool_copy_ms_per_step"])
    ops = dict(trace.reduce(t, KERNELS, top=50)["device_ops"])
    fused = 1e3 * (ops["bitcast_dynamic-update-slice_fusion"]
                   + ops["dynamic-slice_bitcast_fusion"]) / steps
    assert kv == pytest.approx(fused, rel=0.2)
    assert 30.0 < kv < 50.0        # ≈ 39 ms of a 160 ms decode step
