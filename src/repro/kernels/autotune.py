"""Kernel tuning cache: tile shapes as tuned, persisted, CI-tracked data.

PR 5's Pallas kernels hard-code their pipeline geometry — one KV block per
sequential paged-attention grid step, fixed (bm, bn) tiles for the CIM MVM.
This module turns those constants into *looked-up* parameters, the software
analogue of how rad_gen/COFFE sizes SRAM transistors by searching a
parameter space against delay models: `benchmarks/kernel_bench.py
--autotune` times every candidate config through the same harness the CI
perf-trajectory uses, and the winners land in a small JSON cache the
dispatchers consult at trace time.

Cache schema (`pico-ram/tune_cache/v1`)::

    {
      "schema":   "pico-ram/tune_cache/v1",
      "platform": "cpu",                       # jax.default_backend()
      "jax":      "0.4.37",                    # provenance only
      "entries": {
        "paged_attn|decode_w4096|kernel|cpu": {
            "block_size": 64, "kblocks": 8, "row_tile": null,
            "us": 1234.5, "default_us": 5678.9},
        "cim_mvm|m32_g4_n128|pallas|cpu": {
            "bm": 32, "bn": 128, "us": 210.0, "default_us": 260.0}
      }
    }

Every entry is keyed `kernel|shape-family|backend|platform`:

* **kernel** — which dispatcher consults it ("paged_attn" for the
  attention registry's Pallas backend, "cim_mvm" for
  `core.engine.execute_mvm`'s Pallas MVM family);
* **shape-family** — a bucketed shape signature, NOT the exact shape, so
  one tuning run covers a neighborhood: paged attention buckets the KV
  window to the next power of two and splits decode (C = 1) from prefill
  (`decode_w4096`); the MVM buckets rows to the next power of two and
  keys the contraction by its group count (`m32_g4_n128`);
* **backend** — the registry backend name the config applies to;
* **platform** — `jax.default_backend()` at tuning time. A cache tuned on
  CPU interpret mode never leaks onto TPU (and vice versa): lookups from
  a different platform miss and fall back to defaults.

`REPRO_TUNE_CACHE` points at the cache file (`serve.py --tune-cache` sets
it). `kblocks` / `row_tile` (and the MVM `bm` / `bn`) are consumed at
dispatch time; `block_size` is a pool-LAYOUT recommendation — the kernel
takes the pool's pagination as given, so only `serve.py` acts on it, when
sizing a paged pool whose block size wasn't pinned on the command line.
No env / missing file / malformed JSON / wrong schema version all
degrade to an empty cache — dispatch falls back to the built-in defaults,
never errors. The file is re-read when its mtime changes, so a freshly
written cache is picked up without restarting the process.
"""
from __future__ import annotations

import json
import os
import warnings

CACHE_SCHEMA = "pico-ram/tune_cache/v1"
CACHE_ENV = "REPRO_TUNE_CACHE"

# mtime-keyed single-slot memo: (path, mtime) -> entries dict
_STATE: dict = {"key": None, "entries": {}}


def _bucket(n: int) -> int:
    """Round up to the next power of two (shape-family coarsening)."""
    return 1 << max(0, int(n - 1).bit_length())


def attn_family(window: int, c: int) -> str:
    """Shape family for the paged-attention kernel: decode (C = 1) vs
    prefill, window bucketed to the next power of two."""
    mode = "decode" if c == 1 else "prefill"
    return f"{mode}_w{_bucket(window)}"


def mvm_family(m: int, groups: int, n: int) -> str:
    """Shape family for the CIM MVM kernels: rows bucketed, contraction
    keyed by its 144-row group count, output width exact."""
    return f"m{_bucket(m)}_g{groups}_n{n}"


def cache_key(kernel: str, family: str, backend: str,
              platform: str | None = None) -> str:
    if platform is None:
        import jax
        platform = jax.default_backend()
    return "|".join((kernel, family, backend, platform))


def cache_path() -> str | None:
    p = os.environ.get(CACHE_ENV, "").strip()
    return p or None


def load_cache(path: str | None = None) -> dict:
    """Entries dict from `path` (default: $REPRO_TUNE_CACHE), {} on any
    problem — a tuning cache is an accelerant, never a dependency."""
    if path is None:
        path = cache_path()
    if not path:
        return {}
    try:
        mtime = os.path.getmtime(path)
    except OSError:
        return {}
    key = (os.path.abspath(path), mtime)
    if _STATE["key"] == key:
        return _STATE["entries"]
    entries: dict = {}
    try:
        with open(path) as f:
            doc = json.load(f)
        if doc.get("schema") != CACHE_SCHEMA:
            raise ValueError(f"unknown tune-cache schema "
                             f"{doc.get('schema')!r} (want {CACHE_SCHEMA})")
        raw = doc.get("entries", {})
        if not isinstance(raw, dict):
            raise ValueError("tune-cache entries must be an object")
        entries = {str(k): v for k, v in raw.items()
                   if isinstance(v, dict)}
    except (OSError, ValueError) as e:
        from repro.runtime.telemetry import KERNEL_COUNTERS
        KERNEL_COUNTERS.count_fallback()
        warnings.warn(f"ignoring tune cache {path!r}: {e}", stacklevel=2)
        entries = {}
    _STATE["key"] = key
    _STATE["entries"] = entries
    return entries


def lookup(kernel: str, family: str, backend: str,
           platform: str | None = None,
           path: str | None = None) -> dict | None:
    """The tuned config dict for (kernel, family, backend, platform), or
    None on a miss — callers keep their built-in defaults then."""
    entries = load_cache(path)
    if not entries:
        return None
    return entries.get(cache_key(kernel, family, backend, platform))


def save_cache(path: str, entries: dict) -> dict:
    """Write `entries` as a schema-v1 cache file; returns the document."""
    import jax
    doc = {
        "schema": CACHE_SCHEMA,
        "platform": jax.default_backend(),
        "jax": jax.__version__,
        "entries": entries,
    }
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        json.dump(doc, f, indent=1, sort_keys=True)
        f.write("\n")
    os.replace(tmp, path)
    return doc


# ---------------------------------------------------------------------------
# candidate enumeration (timed by benchmarks/kernel_bench.py --autotune)
# ---------------------------------------------------------------------------
def attn_candidates(mb: int, cg: int,
                    block_size: int | None = None) -> list[dict]:
    """Pipeline-shape candidates for a paged-attention shape: kblocks
    divides into the MB block-table width (fewer, wider sequential steps);
    row_tile splits the C·G query rows into parallel tiles; block_size
    (when the caller states the pool's current pagination) proposes
    coarser pool blocks — fewer, larger fetches per window, the knob
    `serve.py --tune-cache` feeds back into the paged-pool layout (the
    kernel itself takes the pool's pagination as given at dispatch time).
    One block per step (kblocks=1, single row tile, the stated
    block_size) is always candidate 0; every kblocks gives the same bits,
    so the choice is one of speed alone."""
    out = [{"block_size": block_size, "kblocks": 1, "row_tile": None}]
    kb = 2
    while kb <= min(mb, 16):
        out.append({"block_size": block_size, "kblocks": kb,
                    "row_tile": None})
        kb *= 2
    if cg > 8:
        best_kb = min(_bucket(mb), 16) if mb > 1 else 1
        out.append({"block_size": block_size, "kblocks": best_kb,
                    "row_tile": max(8, cg // 2)})
    if block_size is not None:
        for mult in (4, 8):
            if mb % mult == 0:
                out.append({"block_size": block_size * mult, "kblocks": 1,
                            "row_tile": None})
    return out


def mvm_candidates(m: int, n: int) -> list[dict]:
    """(bm, bn) tile candidates for the CIM MVM kernels; the built-in
    (128, 128) default first."""
    out = [{"bm": 128, "bn": 128}]
    for bm in (32, 64, 256):
        if bm < 2 * m:
            out.append({"bm": bm, "bn": 128})
    for bn in (64, 256):
        if bn <= n:
            out.append({"bm": 128, "bn": bn})
    return out
