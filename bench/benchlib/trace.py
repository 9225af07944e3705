"""From a profiler trace to device metrics.

Two steps, kept apart so the second can be tested on a small recorded
trace without a chip:

  load(logdir)   the `.xplane.pb` the JAX profiler wrote → a plain dict:
                 {"device": {plane: [[name, start_ns, end_ns, text], ...]},
                  "host": [[name, start_ns, end_ns], ...]}
                 where device events are the ops of each TPU plane's
                 "XLA Ops" line, `text` joins the op's name with its string
                 metadata (HLO op, long name), and host events are the
                 spans of every host thread.
  reduce(t, kernels)
                 busy and idle time of the devices inside the host span
                 `bench.window`, device time per kernel (ops whose text
                 matches the kernel's pattern), the ops that took most time
                 and the longest idle gaps, each labelled by the innermost
                 of the benchmark's host spans (`bench.*`) open at its
                 midpoint.
"""
from __future__ import annotations

import glob
import os
import re

SPAN_PREFIX = "bench."          # the benchmark's own host spans
WINDOW_SPAN = "bench.window"
OPS_LINE = "XLA Ops"
CONTROL_FLOW = {"while", "conditional", "call"}
DEVICE_PREFIX = "/device:TPU:"


def load(logdir: str) -> dict:
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*",
                                          "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    data = ProfileData.from_file(paths[-1])
    device, host = {}, []
    for plane in data.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    stats = dict(ev.stats)
                    text = " ".join([ev.name] + [str(v) for v in stats.values()
                                                 if isinstance(v, str)])
                    ops.append([ev.name, int(ev.start_ns), int(ev.end_ns),
                                text])
            device[plane.name] = ops
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.duration_ns > 0:
                        host.append([ev.name, int(ev.start_ns),
                                     int(ev.end_ns)])
    return {"device": device, "host": host}


def _union(intervals):
    merged = []
    for s, e in sorted(intervals):
        if merged and s <= merged[-1][1]:
            merged[-1][1] = max(merged[-1][1], e)
        else:
            merged.append([s, e])
    return merged


def window_of(t: dict) -> tuple[int, int]:
    spans = [(s, e) for name, s, e in t["host"] if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"no host span {WINDOW_SPAN!r} in the trace")
    return spans[0]


def _label(host, mid) -> str:
    best, best_len = "no bench span", None
    for name, s, e in host:
        if not name.startswith(SPAN_PREFIX) or name == WINDOW_SPAN \
                or not s <= mid <= e:
            continue
        if best_len is None or e - s < best_len:
            best, best_len = name, e - s
    return best


def op_name(name: str) -> str:
    """`%cim_mvm_grouped.83 = f32[...] custom-call(...)` → `cim_mvm_grouped`:
    the HLO instruction's name without its text and its number."""
    head = name.split(" = ", 1)[0].strip().lstrip("%")
    return re.sub(r"(\.\d+)+(\.clone)*$", "", head)


def reduce(t: dict, kernels: dict, top: int = 10) -> dict:
    """kernels: {name: regex}. Seconds throughout; busy_s is averaged over
    the device planes. Control-flow ops (a layer loop's `while`) span the
    ops they run and the gaps between them, so they count for neither."""
    lo, hi = window_of(t)
    pats = {k: re.compile(p) for k, p in kernels.items()}
    busy, kernel_s, kernel_n, by_op, gaps = [], {}, {}, {}, []
    host = [h for h in t["host"] if h[2] > lo and h[1] < hi]
    for plane, ops in sorted(t["device"].items()):
        inside = []
        for name, s, e, text in ops:
            s, e = max(s, lo), min(e, hi)
            short = op_name(name)
            if e <= s or short.split(".")[0] in CONTROL_FLOW:
                continue
            inside.append((s, e))
            dur = (e - s) * 1e-9
            by_op[short] = by_op.get(short, 0.0) + dur
            for k, p in pats.items():
                if p.search(name) or p.search(text):
                    kernel_s[k] = kernel_s.get(k, 0.0) + dur
                    kernel_n[k] = kernel_n.get(k, 0) + 1
        merged = _union(inside)
        busy.append(sum(e - s for s, e in merged) * 1e-9)
        edges = [lo] + [x for iv in merged for x in iv] + [hi]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append(((b - a) * 1e-9,
                             _label(host, (a + b) // 2)))
    n_dev = max(1, len(t["device"]))
    gaps.sort(key=lambda g: -g[0])
    return {
        "window_s": (hi - lo) * 1e-9,
        "busy_s": sum(busy) / n_dev,
        "kernel_s": {k: v / n_dev for k, v in kernel_s.items()},
        "kernel_calls": kernel_n,
        "device_ops": [[n, s / n_dev] for n, s in
                       sorted(by_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[label, s] for s, label in gaps[:top]],
    }
