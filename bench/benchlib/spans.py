"""The program's own spans, scopes and counters, for the metrics that read
them: its host phases (`serve.*` profiler annotations) and named device
scopes from the profiler trace a traced run leaves in `.bench/trace`, and
its process-wide compile counter.

The harness reduces the trace to totals before the metric readers run, so
these readers load the trace again (once per run). A device op's named
scope is its HLO instruction's `op_name` metadata: the profiler keeps each
module's HLO in the trace (plane `/host:metadata`, stat `Hlo Proto`),
and `op_names` reads it with a small protobuf decoder. Where the program
has no such span, scope or counter, each returns None.
"""
from __future__ import annotations

import glob
import os
import re

from . import spec
from . import trace as trace_mod

TRACE_DIR = spec.ROOT / ".bench" / "trace"   # where bench/run.py traces
PHASE_PREFIX = "serve."

# The layer scan's own work: ops whose scope lies under `layer_scan` but
# outside the layer's `attn` and `mlp` (models/transformer.paged_step). On
# the chip these are the slices of each layer's KV pool out of the stacked
# pool (dynamic-slice fusions rooted at the scan's squeeze) and back into
# it (dynamic-update-slice); the weights' slices fuse into attn and mlp.
POOL_COPY = re.compile(r"/layer_scan/(?!.*/(?:attn|mlp)/)")

_cache: dict = {}


def _xplane():
    paths = sorted(glob.glob(os.path.join(str(TRACE_DIR), "plugins",
                                          "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def loaded(ctx):
    """The traced run's raw trace (`trace.load`) with `op_names` under the
    key "op_names", or None without a trace."""
    path = None if ctx.trace is None else _xplane()
    if path is None:
        return None
    key = (path, os.stat(path).st_mtime_ns)
    if key not in _cache:
        _cache.clear()
        t = trace_mod.load(str(TRACE_DIR))
        with open(path, "rb") as f:
            t["op_names"] = op_names(f.read())
        _cache[key] = t
    return _cache[key]


def _fields(buf):
    """(field number, value) of one protobuf message: ints for varints,
    bytes for the rest."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        num, wire = key >> 3, key & 7
        if wire == 0:
            v, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            v, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            v, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire}")
        yield num, v


def _varint(buf, i):
    out = shift = 0
    while True:
        b = buf[i]
        i += 1
        out |= (b & 0x7F) << shift
        shift += 7
        if b < 0x80:
            return out, i


def _field(buf, num, default=b""):
    return next((v for n, v in _fields(buf) if n == num), default)


def op_names(xspace: bytes) -> dict:
    """{HLO instruction name: {op_name metadata}} over every module whose
    HLO the profiler kept in the trace (XSpace → plane `/host:metadata` →
    event metadata → stat `Hlo Proto` → HloModuleProto → computations →
    instructions → OpMetadata.op_name)."""
    out: dict = {}
    for num, plane in _fields(memoryview(xspace)):
        if num != 1 or bytes(_field(plane, 2)) != b"/host:metadata":
            continue
        hlo_stat = {_field(entry, 1, 0) for n, entry in _fields(plane)
                    if n == 5 and bytes(_field(_field(entry, 2), 2))
                    == b"Hlo Proto"}
        for n, entry in _fields(plane):
            if n != 4:
                continue
            for m, stat in _fields(_field(entry, 2)):
                if m != 5 or _field(stat, 1, 0) not in hlo_stat:
                    continue
                module = _field(_field(stat, 6), 1)
                for c, comp in _fields(module):
                    if c != 3:
                        continue
                    for i, instr in _fields(comp):
                        if i != 2:
                            continue
                        name = bytes(_field(instr, 1)).decode()
                        op = bytes(_field(_field(instr, 7), 2)).decode()
                        if op:
                            out.setdefault(name, set()).add(op)
    return out


def phase_ms_per_step(t: dict, phase: str, steps: int):
    """Mean milliseconds per step of the host span `serve.<phase>` over
    the spans inside the traced window; None where there is none."""
    lo, hi = trace_mod.window_of(t)
    name = PHASE_PREFIX + phase
    spans = [e - s for n, s, e in t["host"]
             if n == name and lo <= s and e <= hi]
    if not spans or steps <= 0:
        return None
    return 1e-6 * sum(spans) / steps


def scope_ms_per_step(t: dict, pattern: re.Pattern, steps: int):
    """Mean device milliseconds per step of the ops inside the traced
    window whose scope (`op_name` metadata, `t["op_names"]`) matches
    `pattern`, averaged over the device planes; None where none does."""
    lo, hi = trace_mod.window_of(t)
    scopes = t.get("op_names", {})
    total, found = 0.0, False
    for ops in t["device"].values():
        for name, s, e, _ in ops:
            s, e = max(s, lo), min(e, hi)
            instr = name.split(" = ", 1)[0].strip().lstrip("%")
            if e <= s or trace_mod.op_name(name).split(".")[0] \
                    in trace_mod.CONTROL_FLOW \
                    or not any(pattern.search(o)
                               for o in scopes.get(instr, ())):
                continue
            total += e - s
            found = True
    if not found or steps <= 0:
        return None
    return 1e-6 * total / max(1, len(t["device"])) / steps


def phase_reading(ctx, phase: str):
    t = loaded(ctx)
    if t is None:
        return None
    return phase_ms_per_step(t, phase, len(ctx.traced_steps))


def compile_log():
    """The program's compile events, [(t_end on perf_counter, kind, s)],
    or None where the program counts none."""
    try:
        from repro.runtime.telemetry import KERNEL_COUNTERS
    except ImportError:
        return None
    log = getattr(KERNEL_COUNTERS, "compile_log", None)
    return None if log is None else list(log)
