"""kernels.autotune: tuning-cache round-trip into both dispatchers
(paged attention kblocks/row_tile, CIM MVM bm/bn), shape-family bucketing,
and the malformed/stale-cache fallbacks. Pure cache-plumbing tests run in
both REPRO_FORCE_JNP legs; only the end-to-end kernel-execution check
needs Pallas."""
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels import ops
from repro.kernels import paged_attention as pa
from repro.core.macro import MacroConfig

_FORCED = os.environ.get("REPRO_FORCE_JNP", "").strip().lower() in (
    "1", "true", "yes")
needs_pallas = pytest.mark.skipif(
    _FORCED, reason="direct Pallas kernel tests; REPRO_FORCE_JNP leg is "
                    "jnp-only")


# the pool geometry behind each window below: blocks of 8 positions, eight
# KV heads of 32, float32; eight heads fill a step, so a miss takes
# kblocks = 1
_GEOM = dict(bs=8, kh=8, dh=32, itemsize=4)


def _write_cache(path, entries):
    doc = autotune.save_cache(str(path), entries)
    assert doc["schema"] == autotune.CACHE_SCHEMA
    return str(path)


# ---------------------------------------------------------------------------
# shape families
# ---------------------------------------------------------------------------
def test_shape_families_bucket():
    assert autotune.attn_family(4096, 1) == "decode_w4096"
    assert autotune.attn_family(40, 1) == "decode_w64"    # rounds up
    assert autotune.attn_family(256, 8) == "prefill_w256"
    assert autotune.mvm_family(32, 4, 128) == "m32_g4_n128"
    assert autotune.mvm_family(33, 4, 128) == "m64_g4_n128"


def test_cache_key_includes_platform():
    k = autotune.cache_key("paged_attn", "decode_w64", "kernel")
    assert k.endswith("|" + jax.default_backend())
    assert autotune.cache_key("a", "b", "c", "tpu") == "a|b|c|tpu"


# ---------------------------------------------------------------------------
# round-trip: write → reload → dispatch picks the tuned config
# ---------------------------------------------------------------------------
def test_attn_dispatch_picks_tuned_config(tmp_path, monkeypatch):
    path = _write_cache(tmp_path / "tc.json", {
        autotune.cache_key("paged_attn", "decode_w64", "kernel"):
            {"kblocks": 4, "row_tile": None},
    })
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    # decode window 40 buckets to w64 → tuned hit, clamped to the geometry
    kb, rt = pa._resolve_attn_config(window=40, c=1, mb=5, cg=2, **_GEOM)
    assert (kb, rt) == (4, None)
    # prefill family has no entry → defaults
    assert pa._resolve_attn_config(window=40, c=8, mb=5, cg=16,
                                   **_GEOM) == (1, None)


def test_attn_tuned_config_clamped_to_geometry(tmp_path, monkeypatch):
    path = _write_cache(tmp_path / "tc.json", {
        autotune.cache_key("paged_attn", "decode_w64", "kernel"):
            {"kblocks": 64, "row_tile": 999},
    })
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    kb, rt = pa._resolve_attn_config(window=64, c=1, mb=3, cg=4, **_GEOM)
    assert kb == 3 and rt == 4


def test_mvm_dispatch_picks_tuned_tiles(tmp_path, monkeypatch):
    x = jnp.zeros((8, 288))
    fam = autotune.mvm_family(8, 2, 64)
    path = _write_cache(tmp_path / "tc.json", {
        autotune.cache_key("cim_mvm", fam, "pallas"): {"bm": 32, "bn": 64},
    })
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    assert ops._resolve_tiles(x, 64, 144, None, None) == (32, 64)
    # explicit kwargs always win over the cache
    assert ops._resolve_tiles(x, 64, 144, 16, 16) == (16, 16)
    # a different shape family misses → (128, 128) defaults
    assert ops._resolve_tiles(jnp.zeros((8, 144)), 64, 144,
                              None, None) == (128, 128)


def test_platform_mismatch_is_a_miss(tmp_path, monkeypatch):
    path = _write_cache(tmp_path / "tc.json", {
        autotune.cache_key("paged_attn", "decode_w64", "kernel",
                           platform="tpu-v9"): {"kblocks": 8},
    })
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    assert pa._resolve_attn_config(window=64, c=1, mb=8, cg=2,
                                   **_GEOM) == (1, None)


def test_cache_reloads_on_rewrite(tmp_path, monkeypatch):
    """A freshly rewritten cache file is picked up without restarting."""
    key = autotune.cache_key("paged_attn", "decode_w64", "kernel")
    path = _write_cache(tmp_path / "tc.json", {key: {"kblocks": 2}})
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    assert pa._resolve_attn_config(window=64, c=1, mb=8, cg=2,
                                   **_GEOM)[0] == 2
    os.utime(_write_cache(tmp_path / "tc.json", {key: {"kblocks": 8}}),
             (1e9, 1e9))  # force a distinct mtime even on coarse clocks
    assert pa._resolve_attn_config(window=64, c=1, mb=8, cg=2,
                                   **_GEOM)[0] == 8


@needs_pallas
def test_tuned_attn_end_to_end_matches_exact(tmp_path, monkeypatch):
    """The full dispatch chain under a tuned cache: paged_attention with
    backend="kernel" runs the kblocks>1 pipeline and still matches exact."""
    from tests.test_paged_attention import _make_case
    case = _make_case(61, b=2, mb=8, c=1)
    q, kp, vp, tables, positions, kvl = case
    o_ref = pa.paged_attention(q, kp, vp, tables, positions=positions,
                               kv_len=kvl, backend="exact")
    path = _write_cache(tmp_path / "tc.json", {
        autotune.cache_key("paged_attn", "decode_w64", "kernel"):
            {"kblocks": 4, "row_tile": None},
    })
    monkeypatch.setenv(autotune.CACHE_ENV, path)
    o = pa.paged_attention(q, kp, vp, tables, positions=positions,
                           kv_len=kvl, backend="kernel")
    assert jnp.allclose(o, o_ref, atol=2e-5, rtol=2e-5)


# ---------------------------------------------------------------------------
# malformed / stale caches degrade to defaults, never error
# ---------------------------------------------------------------------------
def test_missing_cache_file_is_empty(monkeypatch):
    monkeypatch.setenv(autotune.CACHE_ENV, "/nonexistent/tune.json")
    assert autotune.load_cache() == {}
    assert pa._resolve_attn_config(window=64, c=1, mb=8, cg=2,
                                   **_GEOM) == (1, None)


def test_no_env_is_empty(monkeypatch):
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    assert autotune.load_cache() == {}


def test_malformed_json_falls_back(tmp_path, monkeypatch):
    p = tmp_path / "bad.json"
    p.write_text("{not json")
    monkeypatch.setenv(autotune.CACHE_ENV, str(p))
    with pytest.warns(UserWarning, match="ignoring tune cache"):
        assert autotune.load_cache() == {}
    assert pa._resolve_attn_config(window=64, c=1, mb=8, cg=2,
                                   **_GEOM) == (1, None)


def test_stale_schema_falls_back(tmp_path, monkeypatch):
    p = tmp_path / "stale.json"
    p.write_text(json.dumps({"schema": "pico-ram/tune_cache/v0",
                             "entries": {"x": {"kblocks": 8}}}))
    monkeypatch.setenv(autotune.CACHE_ENV, str(p))
    with pytest.warns(UserWarning, match="schema"):
        assert autotune.load_cache() == {}


def test_non_dict_entries_dropped(tmp_path, monkeypatch):
    p = tmp_path / "odd.json"
    p.write_text(json.dumps({"schema": autotune.CACHE_SCHEMA,
                             "entries": {"a": [1, 2], "b": {"bm": 64}}}))
    monkeypatch.setenv(autotune.CACHE_ENV, str(p))
    assert autotune.load_cache() == {"b": {"bm": 64}}


# ---------------------------------------------------------------------------
# candidate enumeration (what kernel_bench --autotune times)
# ---------------------------------------------------------------------------
def test_attn_candidates_default_first():
    cands = autotune.attn_candidates(512, 2)
    assert cands[0] == {"block_size": None, "kblocks": 1, "row_tile": None}
    assert {"block_size": None, "kblocks": 8, "row_tile": None} in cands
    assert all(c["kblocks"] <= 16 for c in cands)


def test_attn_candidates_block_size_axis():
    """Stating the pool's pagination adds coarser-block layout candidates
    (consumed by serve.py, not the dispatcher); the default stays first."""
    cands = autotune.attn_candidates(256, 4, block_size=16)
    assert cands[0] == {"block_size": 16, "kblocks": 1, "row_tile": None}
    sizes = {c["block_size"] for c in cands}
    assert {16, 64, 128} <= sizes
    # mb=6 is not divisible by 4 or 8 → no coarser layouts proposed
    assert all(c["block_size"] == 8
               for c in autotune.attn_candidates(6, 4, block_size=8))


def test_mvm_candidates_default_first():
    cands = autotune.mvm_candidates(128, 128)
    assert cands[0] == {"bm": 128, "bn": 128}
    assert len(cands) > 1
