"""Paged-attention kernel subsystem: registry contracts, Pallas-kernel vs
exact-reference parity (decode + chunked prefill, GQA shapes, windows
spanning ≥ 4 blocks), trash-block NaN/garbage hardening, and the
1-device-mesh shard_map bit-identity.

The Pallas tests run the kernel in interpret mode (CPU CI); under
REPRO_FORCE_JNP=1 the explicit-kernel tests skip — that leg models an
environment without interpret-mode Pallas, where auto-selection must pin
the exact backend (which IS tested in that leg).
"""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import autotune
from repro.kernels import paged_attention as pa
from repro.parallel import sharding

_FORCED = os.environ.get("REPRO_FORCE_JNP", "").strip().lower() in (
    "1", "true", "yes")
needs_pallas = pytest.mark.skipif(
    _FORCED, reason="direct Pallas kernel tests; REPRO_FORCE_JNP leg is "
                    "jnp-only")


def _make_case(seed, *, b=3, kh=2, g=2, dh=32, bs=8, mb=5, c=1,
               full_depth=False):
    """Random pool + block tables + per-slot depths for a C-wide step.

    Returns everything both backends consume. Depths are mixed across
    slots (or pinned to the deepest window with full_depth); allocated
    blocks are distinct ids >= 1, unallocated table entries point at the
    trash block 0 — exactly the runtime.paging layout.
    """
    rng = np.random.RandomState(seed)
    key = jax.random.PRNGKey(seed)
    w = mb * bs
    nb = b * mb + 1
    q = jax.random.normal(key, (b, c, kh * g, dh), jnp.float32)
    kp = jax.random.normal(jax.random.fold_in(key, 1), (nb, kh, bs, dh),
                           jnp.float32)
    vp = jax.random.normal(jax.random.fold_in(key, 2), (nb, kh, bs, dh),
                           jnp.float32)
    if full_depth:
        lens = np.full(b, w - c, np.int64)
    else:
        lens = np.array([rng.randint(0, w - c + 1) for _ in range(b)])
    kvl = lens + c
    # distinct physical blocks per slot, trash block elsewhere
    free = list(range(1, nb))
    rng.shuffle(free)
    tables = np.zeros((b, mb), np.int32)
    for s in range(b):
        need = -(-int(kvl[s]) // bs)
        for j in range(need):
            tables[s, j] = free.pop()
    positions = jnp.asarray(lens[:, None] + np.arange(c), jnp.int32)
    return (q, kp, vp, jnp.asarray(tables), positions,
            jnp.asarray(kvl, jnp.int32))


def _run(backend, case):
    q, kp, vp, tables, positions, kvl = case
    return pa.paged_attention(q, kp, vp, tables, positions=positions,
                              kv_len=kvl, backend=backend)


# ---------------------------------------------------------------------------
# registry
# ---------------------------------------------------------------------------
def test_registry_contents():
    assert {"exact", "kernel"} <= set(pa.available_attn_backends())
    assert pa.get_attn_backend("exact").name == "exact"
    assert pa.get_attn_backend("kernel").pallas
    with pytest.raises(ValueError, match="unknown attention backend"):
        pa.get_attn_backend("nope")
    with pytest.raises(ValueError):
        pa.choose_attn_backend("nope")


def test_auto_resolution(monkeypatch):
    monkeypatch.delenv("REPRO_FORCE_JNP", raising=False)
    assert pa.choose_attn_backend("auto") == "kernel"
    assert pa.choose_attn_backend("exact") == "exact"
    monkeypatch.setenv("REPRO_FORCE_JNP", "1")
    assert pa.choose_attn_backend("auto") == "exact"
    # explicit names bypass the env pin, like the CIM engine's backends
    assert pa.choose_attn_backend("kernel") == "kernel"


# ---------------------------------------------------------------------------
# kernel vs exact parity
# ---------------------------------------------------------------------------
@needs_pallas
@pytest.mark.parametrize("kh,g", [(1, 4), (2, 2), (4, 1)])
def test_decode_parity_gqa_shapes(kh, g):
    """C=1 decode at mixed depths over a 5-block window, for MHA/GQA/MQA
    group shapes."""
    case = _make_case(11 + kh, kh=kh, g=g, c=1)
    o_exact = _run("exact", case)
    o_kernel = _run("kernel", case)
    assert o_kernel.shape == o_exact.shape
    assert jnp.allclose(o_kernel, o_exact, atol=2e-5, rtol=2e-5), \
        float(jnp.max(jnp.abs(o_kernel - o_exact)))


@needs_pallas
@pytest.mark.parametrize("c", [2, 5, 8])
def test_prefill_chunk_parity(c):
    """C-wide prefill chunks (causal within the chunk, windows ≥ 4 blocks)
    agree with the exact one-pass softmax."""
    case = _make_case(23 + c, b=2, mb=6, c=c)
    o_exact = _run("exact", case)
    o_kernel = _run("kernel", case)
    assert jnp.allclose(o_kernel, o_exact, atol=2e-5, rtol=2e-5), \
        float(jnp.max(jnp.abs(o_kernel - o_exact)))


@needs_pallas
def test_full_window_decode_parity():
    """Deepest possible decode: every table entry allocated, the query at
    the last position of the window."""
    case = _make_case(5, b=2, mb=4, c=1, full_depth=True)
    assert jnp.allclose(_run("kernel", case), _run("exact", case),
                        atol=2e-5, rtol=2e-5)


@needs_pallas
def test_idle_lane_outputs_finite():
    """kv_len = 0 lanes (idle slots in a mixed batch) must emit finite
    values from both backends — their outputs are discarded, but NaN would
    poison the whole jit output buffer check."""
    q, kp, vp, tables, positions, kvl = _make_case(7, b=2, c=1)
    kvl = kvl.at[0].set(0)
    positions = positions.at[0].set(0)
    tables = tables.at[0].set(0)
    for backend in ("exact", "kernel"):
        o = pa.paged_attention(q, kp, vp, tables, positions=positions,
                               kv_len=kvl, backend=backend)
        assert bool(jnp.all(jnp.isfinite(o))), backend


# ---------------------------------------------------------------------------
# trash-block hardening: NaN/garbage in never-attended storage
# ---------------------------------------------------------------------------
@needs_pallas
@pytest.mark.parametrize("poison", [float("nan"), 1e6, -1e6])
def test_trash_block_poison_invariance(poison):
    """Physical block 0 (masked-lane writes, unallocated table entries) is
    never read at non-zero softmax weight — poisoning it with NaN or huge
    garbage must not change either backend's output by a single bit.
    NaN is the adversarial case: a masked weight of exactly 0 still turns
    into NaN through 0·NaN unless the V rows are sanitized."""
    case = _make_case(31, b=3, mb=5, c=1)
    q, kp, vp, tables, positions, kvl = case
    kp_p = kp.at[0].set(poison)
    vp_p = vp.at[0].set(poison)
    for backend in ("exact", "kernel"):
        clean = pa.paged_attention(q, kp, vp, tables, positions=positions,
                                   kv_len=kvl, backend=backend)
        dirty = pa.paged_attention(q, kp_p, vp_p, tables,
                                   positions=positions, kv_len=kvl,
                                   backend=backend)
        assert jnp.array_equal(clean, dirty), backend


@needs_pallas
def test_stale_block_tail_poison_invariance():
    """Positions past kv_len INSIDE an allocated block (the stale tail a
    LIFO-reused block carries) are masked too: poison every pool position
    at or past each slot's kv_len and require bit-identical outputs."""
    case = _make_case(37, b=2, mb=4, c=3)
    q, kp, vp, tables, positions, kvl = case
    bs = kp.shape[2]
    kp_p, vp_p = np.asarray(kp).copy(), np.asarray(vp).copy()
    for s in range(tables.shape[0]):
        for j, blk in enumerate(np.asarray(tables[s])):
            if blk == 0:
                continue
            off = int(kvl[s]) - j * bs
            if off < bs:
                kp_p[blk, :, max(off, 0):] = np.nan
                vp_p[blk, :, max(off, 0):] = np.nan
    for backend in ("exact", "kernel"):
        clean = pa.paged_attention(q, kp, vp, tables, positions=positions,
                                   kv_len=kvl, backend=backend)
        dirty = pa.paged_attention(q, jnp.asarray(kp_p), jnp.asarray(vp_p),
                                   tables, positions=positions, kv_len=kvl,
                                   backend=backend)
        assert jnp.array_equal(clean, dirty), backend


# ---------------------------------------------------------------------------
# multi-block pipeline (kblocks > 1) and wide row tiles
# ---------------------------------------------------------------------------
# (c, kblocks, kh): decode and chunk steps at kblocks 2, 4 and 8, and
# programs holding four KV heads
_KBLOCKS_CASES = [(c, kb, 2) for c in (1, 4) for kb in (2, 4, 8)] \
    + [(1, 8, 4), (4, 8, 4)]


@needs_pallas
@pytest.mark.parametrize(
    "c,kblocks,kh",
    [pytest.param(*p, id=f"{p[0]}-{p[1]}" + (f"-kh{p[2]}" if p[2] != 2
                                              else ""))
     for p in _KBLOCKS_CASES])
def test_kblocks_parity(c, kblocks, kh):
    """Fetching kblocks KV blocks per sequential grid step must give the
    single-block pipeline's output bit for bit (the blocks of a step are
    folded in one after another, head by head) and match the exact backend
    at mixed depths, for both decode and chunked prefill, with G = 2 query
    heads per KV head. mb=9 is not divisible by 2, 4 or 8, so the
    block-table padding (trash block 0 on the tail) is exercised too."""
    case = _make_case(53 + kblocks + c, b=3, kh=kh, g=2, mb=9, c=c)
    q, kp, vp, tables, positions, kvl = case
    lens = kvl - c
    o_one = pa.paged_flash_attention(q, kp, vp, tables, lens, kvl, kblocks=1)
    o_multi = pa.paged_flash_attention(q, kp, vp, tables, lens, kvl,
                                       kblocks=kblocks)
    o_exact = _run("exact", case)
    assert jnp.array_equal(o_multi, o_one), \
        float(jnp.max(jnp.abs(o_multi - o_one)))
    assert jnp.allclose(o_multi, o_exact, atol=2e-5, rtol=2e-5)


@needs_pallas
@pytest.mark.parametrize("row_tile", [3, 4])
def test_row_tile_parity(row_tile):
    """Wider C·G row tiles (dividing and non-dividing — the latter pads the
    folded q rows) give the single-tile, single-block kernel's output bit
    for bit, and agree with exact."""
    case = _make_case(59 + row_tile, b=2, kh=2, g=2, mb=6, c=4)  # cg = 8
    q, kp, vp, tables, positions, kvl = case
    lens = kvl - 4
    o_one = pa.paged_flash_attention(q, kp, vp, tables, lens, kvl, kblocks=1)
    o_tiled = pa.paged_flash_attention(q, kp, vp, tables, lens, kvl,
                                       kblocks=2, row_tile=row_tile)
    assert jnp.array_equal(o_tiled, o_one), \
        float(jnp.max(jnp.abs(o_tiled - o_one)))
    assert jnp.allclose(o_tiled, _run("exact", case), atol=2e-5, rtol=2e-5)


def test_default_kblocks_from_shape(monkeypatch):
    """With no tuning cache, kblocks spans about 128 positions, unrolls at
    most 8 tile updates (kblocks × KH) a step, clamps to a short table, and
    keeps the double-buffered K + V blocks within the VMEM cap; one row
    tile."""
    monkeypatch.delenv(autotune.CACHE_ENV, raising=False)
    # internlm2-1.8b in bf16: 8 KV heads fill the step with one block
    geom = dict(bs=16, kh=8, dh=128, itemsize=2)
    for c, cg in ((1, 2), (16, 32)):
        assert pa._resolve_attn_config(window=2048, c=c, mb=128, cg=cg,
                                       **geom) == (1, None)
    # one KV head: 8 blocks of 16, a 128-position span; 2 heads: 4 blocks
    one = dict(geom, kh=1)
    assert pa._resolve_attn_config(window=2048, c=1, mb=128, cg=8,
                                   **one) == (8, None)
    assert pa._resolve_attn_config(window=2048, c=1, mb=128, cg=8,
                                   **dict(geom, kh=2)) == (4, None)
    # a short table clamps to its geometry
    assert pa._resolve_attn_config(window=80, c=1, mb=5, cg=8,
                                   **one) == (5, None)
    # one float32 head of 4096: 256 KiB a block, so four blocks a step
    wide = dict(bs=16, kh=1, dh=4096, itemsize=4)
    kb, _ = pa._resolve_attn_config(window=2048, c=1, mb=128, cg=8, **wide)
    assert kb == 4
    assert 2 * 2 * kb * 1 * 16 * 4096 * 4 <= pa._ATTN_KV_VMEM
    # blocks wider than the span: one block a step
    assert pa._resolve_attn_config(window=2048, c=1, mb=8, cg=8, bs=256,
                                   kh=1, dh=128, itemsize=2) == (1, None)


@needs_pallas
@pytest.mark.parametrize("poison", [float("nan"), 1e6])
def test_kblocks_trash_poison_invariance(poison):
    """The padded table tail and masked sub-blocks of the multi-block fetch
    all point at trash block 0 — poisoning it must not move a bit even when
    several sub-blocks of one fetch straddle the valid/trash boundary."""
    case = _make_case(67, b=3, mb=5, c=1)
    q, kp, vp, tables, positions, kvl = case
    lens = kvl - 1
    kp_p = kp.at[0].set(poison)
    vp_p = vp.at[0].set(poison)
    for kwargs in ({"kblocks": 4}, {"kblocks": 2, "row_tile": 2}):
        clean = pa.paged_flash_attention(q, kp, vp, tables, lens, kvl,
                                         **kwargs)
        dirty = pa.paged_flash_attention(q, kp_p, vp_p, tables, lens, kvl,
                                         **kwargs)
        assert jnp.array_equal(clean, dirty), kwargs


# ---------------------------------------------------------------------------
# fused decode write-scatter
# ---------------------------------------------------------------------------
@needs_pallas
def test_fused_write_bit_identity():
    """fused_paged_write must land each slot's new K/V row bit-identically
    to the host-side paged_write on every real block; the trash block (the
    one deliberate divergence: invalid lanes become no-ops instead of trash
    writes) is untouched."""
    from repro.models import common
    case = _make_case(71, b=3, mb=5, c=1)
    q, kp, vp, tables, positions, kvl = case
    b, kh, bs, dh = q.shape[0], *kp.shape[1:]
    key = jax.random.PRNGKey(91)
    new_k = jax.random.normal(key, (b, 1, kh, dh), jnp.float32)
    new_v = jax.random.normal(jax.random.fold_in(key, 1), (b, 1, kh, dh),
                              jnp.float32)
    # valid write targets: slot s appends at kv_len[s]-1 inside its last
    # allocated block; slot 0 is forced invalid (flat_idx 0)
    flat = []
    for s in range(b):
        pos = int(kvl[s]) - 1
        blk = int(tables[s, pos // bs])
        flat.append(blk * bs + pos % bs)
    flat[0] = 0
    flat_idx = jnp.asarray(flat, jnp.int32)[:, None]
    ref_k = common.paged_write(kp, new_k, flat_idx)
    ref_v = common.paged_write(vp, new_v, flat_idx)
    got_k, got_v = pa.fused_paged_write(kp, vp, new_k, new_v, flat_idx)
    assert jnp.array_equal(ref_k[1:], got_k[1:])
    assert jnp.array_equal(ref_v[1:], got_v[1:])
    # trash block: fused keeps the original storage (no-op write)
    assert jnp.array_equal(got_k[0], kp[0])
    assert jnp.array_equal(got_v[0], vp[0])
    # and attention over the written pools agrees bit-for-bit, since the
    # divergent bits live in storage that is never read unmasked
    o_ref = pa.paged_attention(q, ref_k, ref_v, tables, positions=positions,
                               kv_len=kvl, backend="kernel")
    o_got = pa.paged_attention(q, got_k, got_v, tables, positions=positions,
                               kv_len=kvl, backend="kernel")
    assert jnp.array_equal(o_ref, o_got)


# ---------------------------------------------------------------------------
# mesh dispatch
# ---------------------------------------------------------------------------
@needs_pallas
def test_one_device_mesh_bit_identity():
    """The shard_map wrapping on a 1-device mesh must be bit-identical to
    the plain kernel call (the same contract the CIM engine pins)."""
    from repro.launch.mesh import make_host_mesh
    case = _make_case(41, b=2, c=1)
    ref = _run("kernel", case)
    sharding.set_mesh(make_host_mesh(1, 1))
    try:
        meshed = _run("kernel", case)
    finally:
        sharding.set_mesh(None)
    assert jnp.array_equal(ref, meshed)


def test_exact_backend_matches_pre_registry_math():
    """The exact backend IS the PR-4 path: gather + decode_attention /
    paged_prefill_attention, with the V sanitization a bit-exact no-op on
    clean pools."""
    from repro.models import common
    for c in (1, 4):
        case = _make_case(47 + c, b=2, c=c)
        q, kp, vp, tables, positions, kvl = case
        k_win = common.paged_gather(kp, tables)
        v_win = common.paged_gather(vp, tables)
        if c == 1:
            ref = common.decode_attention(q, k_win, v_win,
                                          kvl[:, None, None, None])
        else:
            ref = common.paged_prefill_attention(q, k_win, v_win,
                                                 positions, kvl)
        got = _run("exact", case)
        assert jnp.array_equal(ref, got)
