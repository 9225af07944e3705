"""Paged-attention kernel subsystem: block-table flash attention + registry.

PR 4's paged-KV server reads each slot's KV window by gathering its block
table on the host side of the math (`models.common.paged_gather`) and then
attending with an exact one-pass softmax — which materializes the full
`[B, C, KH, G, W]` score tensor. Fine at smoke scale; at a 32k window that
tensor is the whole memory budget. This module is the TPU-scale fix, built
the same way the CIM execution engine was: a small registry of ATTENTION
backends that all consume the paged pool + block tables directly, so the
serving step (`models.transformer.paged_step`) selects its attention path
exactly like layer matmuls select their CIM backend.

  backend   what it does                                         runs on
  --------  ---------------------------------------------------  ---------
  "exact"   the PR-4 reference path: gather each slot's window   any
            through its table, one-pass softmax over the full
            window (models.common.decode_attention /
            paged_prefill_attention — the bit-identity anchors)
  "kernel"  fused Pallas flash kernel: the block gather happens  TPU (or
            INSIDE the kernel (block tables are scalar-          interpret
            prefetched and drive the K/V BlockSpec index maps),  mode on
            and the softmax is accumulated online block-by-      CPU)
            block in VMEM — the [B, C, KH, G, W] score tensor
            never exists; live scores are one [C·G, bs] tile
  "auto"    "kernel", unless REPRO_FORCE_JNP=1 pins "exact"
            (the same escape hatch the CIM engine honors for
            environments without interpret-mode Pallas)

Kernel layout (grid = (B, KH, MB), MB = blocks per slot window):

  * the two leading grid axes are parallel (one program per slot × KV
    head); the block axis is sequential ("arbitrary") and innermost so the
    [C·G, dh] output accumulator plus the online-softmax running max/sum
    stay resident in VMEM across all MB blocks — the same
    revisit-nothing-in-HBM discipline as the fused CIM MVM kernel;
  * the block tables (and per-slot base positions / valid lengths) ride in
    as scalar-prefetch operands: the K/V BlockSpec index maps read
    `tables[b, j]`, so the pool block each grid step DMAs into VMEM IS the
    slot's j-th logical block — a gather the kernel gets for free from the
    pipeline, with no [B, W, KH, dh] windowed copy ever materialized. The
    pools are head-major inside a block ([NB, KH, bs, dh]), so one fetch is
    a (1, 1, bs, dh) block whose last two dims equal the pool's — the
    block shape Mosaic accepts for any bs and dh;
  * GQA is folded as rows: q arrives [B, KH, C·G, dh] (C = chunk width, G
    = query heads per KV head), so decode (C=1) and chunked prefill are
    the SAME kernel — the causal mask per row uses that row's chunk
    offset (row // G), mirroring `paged_prefill_attention`'s mask exactly;
  * trash-block lanes (physical block 0 — masked writes, unallocated table
    entries) sit at positions >= the slot's kv_len and are masked at -1e30
    before the online max; their probabilities are forced to exactly 0 and
    their V rows are zeroed before the PV dot, so even NaN poison in the
    trash block cannot reach the output (0·NaN is NaN — masking the weight
    alone would not be enough). The "exact" backend applies the same V
    sanitization outside the softmax, where it is a bit-exact no-op for
    clean pools.

Mesh composition: a bare `pallas_call` cannot be GSPMD-partitioned, so when
a mesh is active the dispatcher wraps the kernel in
`parallel.sharding.shard_map` with KV heads sharded over "model" (when
divisible — the serving head layout; everything else replicated, B is
small). Callers already tracing per-shard (`sharding.in_shard_context()`)
get the plain kernel. The "exact" backend stays plain jnp and lets GSPMD
partition it.
"""
from __future__ import annotations

import dataclasses
import functools
import math
import os
from typing import Callable

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec

from repro.kernels import interpret_mode
from repro.parallel import sharding
from repro.runtime.telemetry import KERNEL_COUNTERS

# ---------------------------------------------------------------------------
# backend registry (mirrors core.engine's CIM backend registry)
# ---------------------------------------------------------------------------
@dataclasses.dataclass(frozen=True)
class AttnBackendSpec:
    """One paged-attention evaluation strategy.

    fn(q, k_pool, v_pool, tables, positions, kv_len) -> o
      q [B, C, H, dh] (C = 1 for decode); pools [NB, KH, bs, dh];
      tables [B, MB] physical block ids; positions [B, C] absolute query
      positions (= lens + chunk offset); kv_len [B] tokens valid in the
      window INCLUDING this step's writes. Returns [B, C, H, dh].
    """

    name: str
    fn: Callable
    pallas: bool = False   # True → wants the shard_map mesh dispatch


_ATTN_REGISTRY: dict[str, AttnBackendSpec] = {}


def register_attn_backend(name: str, *, pallas: bool = False):
    """Register a paged-attention backend under `name` (decorator)."""
    def deco(fn):
        _ATTN_REGISTRY[name] = AttnBackendSpec(name, fn, pallas)
        return fn
    return deco


def get_attn_backend(name: str) -> AttnBackendSpec:
    try:
        return _ATTN_REGISTRY[name]
    except KeyError:
        raise ValueError(f"unknown attention backend {name!r}; "
                         f"registered: {sorted(_ATTN_REGISTRY)}") from None


def available_attn_backends() -> tuple[str, ...]:
    return tuple(sorted(_ATTN_REGISTRY))


def _force_jnp() -> bool:
    """REPRO_FORCE_JNP=1 pins auto-selection to the jnp reference — the
    same escape hatch core.engine honors (environments without interpret-
    mode Pallas). Explicit backend names bypass it."""
    return os.environ.get("REPRO_FORCE_JNP", "").strip().lower() \
        in ("1", "true", "yes")


def choose_attn_backend(backend: str) -> str:
    """Resolve "auto" (or an explicit name) to a registered backend."""
    if backend != "auto":
        return get_attn_backend(backend).name
    return "exact" if _force_jnp() else "kernel"


# ---------------------------------------------------------------------------
# "exact" backend: the PR-4 gather + one-pass-softmax reference path
# ---------------------------------------------------------------------------
@register_attn_backend("exact")
def _exact_attention(q, k_pool, v_pool, tables, positions, kv_len):
    """Window gather through the table + the dense-cache attention math.

    Literally the pre-registry serving path (models.common.paged_gather →
    decode_attention / paged_prefill_attention), kept as the bit-identity
    anchor the paged soak tests pin. One hardening addition: V rows at
    positions >= kv_len (trash block / stale block tails) are zeroed
    BEFORE the PV contraction. Their softmax weight is already exactly 0
    (exp(-1e30 - m) underflows), so this is bit-exact for clean pools —
    but 0 · NaN = NaN, so without it NaN poison in never-attended storage
    would still reach the output.
    """
    from repro.models import common  # lazy: kernels must not import models
    k_win = common.paged_gather(k_pool, tables)
    v_win = common.paged_gather(v_pool, tables)
    w = k_win.shape[1]
    valid = (jnp.arange(w)[None, :] < kv_len[:, None])
    # jnp.where, not a mask multiply: 0 · NaN is NaN, so multiplying would
    # let NaN poison through the very rows being sanitized
    v_win = jnp.where(valid[..., None, None], v_win,
                      jnp.zeros((), v_win.dtype))
    if q.shape[1] == 1:
        # same window shape + mask math as the dense slot cache → decode
        # stays bit-identical to the unpaged decode_attention path
        return common.decode_attention(q, k_win, v_win,
                                       kv_len[:, None, None, None])
    return common.paged_prefill_attention(q, k_win, v_win, positions, kv_len)


# ---------------------------------------------------------------------------
# "kernel" backend: fused Pallas flash decode/prefill over block tables
# ---------------------------------------------------------------------------
def _paged_attn_kernel(tables_ref, lens_ref, kvl_ref, q_ref, *refs,
                       scale: float, block_size: int, g: int, kblocks: int,
                       row_tile: int):
    """One (slot b, KV head h, row tile r) program; sequential pass over
    the MB blocks, `kblocks` logical blocks per step.

    q_ref [1, 1, RT, dh] (RT = row tile of the C·G query rows); the step's
    KV arrives as `kblocks` separate [1, 1, bs, dh] refs — the slot's
    logical blocks j·kblocks … j·kblocks+kblocks−1, each fetched by its own
    index map through the scalar-prefetched table, so the pipeline double-
    buffers a [kblocks·bs, dh] span per sequential step. Scratch holds the
    online-softmax state (running max m, sum l, PV accumulator) in VMEM for
    the whole pass; the only score tensor ever live is the
    [RT, kblocks·bs] tile of this step.
    """
    k_refs = refs[:kblocks]
    v_refs = refs[kblocks:2 * kblocks]
    o_ref = refs[2 * kblocks]
    m_ref, l_ref, acc_ref = refs[2 * kblocks + 1:]
    b = pl.program_id(0)
    r = pl.program_id(2)
    j = pl.program_id(3)
    span = kblocks * block_size

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    kvl = kvl_ref[b]

    # Steps whose whole span sits at or past the slot's valid length hold
    # nothing attendable (every position masks to weight 0) — skip their
    # MXU work entirely; their table entries point at the trash block
    # anyway (including the pad entries appended to make MB divide).
    @pl.when(j * span < kvl)
    def _block():
        q = q_ref[0, 0].astype(jnp.float32)            # [RT, dh]
        k = jnp.concatenate(                           # [span, dh]
            [kr[0, 0] for kr in k_refs], axis=0).astype(jnp.float32)
        v = jnp.concatenate(
            [vr[0, 0] for vr in v_refs], axis=0).astype(jnp.float32)
        rt = q.shape[0]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos_s = j * span \
            + jax.lax.broadcasted_iota(jnp.int32, (rt, span), 1)
        row = r * rt + jax.lax.broadcasted_iota(jnp.int32, (rt, span), 0)
        chunk_off = row // g
        pos_q = lens_ref[b] + chunk_off
        # the paged_prefill_attention mask exactly: causal within the chunk
        # AND inside the slot's valid window (trash/stale lanes land here)
        mask = (pos_s <= pos_q) & (pos_s < kvl)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        # exp(-1e30 - m) underflows to 0, but force masked weights to an
        # exact 0 so an all-masked tile cannot normalize to uniform
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        # zero invalid V rows pre-dot via where (0-weight · NaN-garbage is
        # still NaN, and so is 0 · NaN from a mask multiply)
        pos_v = j * span \
            + jax.lax.broadcasted_iota(jnp.int32, (span, 1), 0)
        v = jnp.where(pos_v < kvl, v, 0.0)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha \
            + jnp.dot(p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(3) - 1)
    def _finish():
        # idle lanes (kv_len = 0) keep l = 0 → emit 0, never NaN; their
        # outputs are discarded by the scheduler anyway
        o_ref[0, 0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit,
                   static_argnames=("block_size", "g", "interpret",
                                    "kblocks", "row_tile"))
def _paged_attn_call(q3, k_pool, v_pool, tables, lens, kvl, *,
                     block_size: int, g: int, interpret: bool,
                     kblocks: int = 1, row_tile: int | None = None):
    """pallas_call plumbing: q3 [B, KH, CG, dh] f32 → o [B, KH, CG, dh].

    `tables` must already be padded to a multiple of `kblocks` (pad entries
    point at the trash block); CG must divide by `row_tile`.
    """
    b, kh, cg, dh = q3.shape
    mb = tables.shape[1]
    assert mb % kblocks == 0, (mb, kblocks)
    rt = cg if row_tile is None else row_tile
    assert cg % rt == 0, (cg, rt)
    kern = functools.partial(_paged_attn_kernel,
                             scale=1.0 / math.sqrt(dh),
                             block_size=block_size, g=g, kblocks=kblocks,
                             row_tile=rt)

    def _kv_map(i):
        # i-th sub-block of the step's kblocks-wide span; default-arg bind
        # so each spec closes over its own stride offset
        return lambda b, h, r, j, t, ln, kv, i=i: (t[b, j * kblocks + i],
                                                   h, 0, 0)

    kv_spec = [pl.BlockSpec((1, 1, block_size, dh), _kv_map(i))
               for i in range(kblocks)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b, kh, cg // rt, mb // kblocks),
        in_specs=[
            pl.BlockSpec((1, 1, rt, dh),
                         lambda b, h, r, j, t, ln, kv: (b, h, r, 0)),
            *kv_spec,          # kblocks K blocks …
            *kv_spec,          # … then the matching V blocks
        ],
        out_specs=pl.BlockSpec((1, 1, rt, dh),
                               lambda b, h, r, j, t, ln, kv: (b, h, r, 0)),
        scratch_shapes=[
            pltpu.VMEM((rt, 1), jnp.float32),    # running max m
            pltpu.VMEM((rt, 1), jnp.float32),    # running sum l
            pltpu.VMEM((rt, dh), jnp.float32),   # PV accumulator
        ],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, kh, cg, dh), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "parallel",
                                 "arbitrary")),
        interpret=interpret,
    )(tables.astype(jnp.int32), lens.astype(jnp.int32),
      kvl.astype(jnp.int32), q3.astype(jnp.float32),
      *([k_pool] * kblocks), *([v_pool] * kblocks))


def _resolve_attn_config(*, window: int, c: int, mb: int, cg: int):
    """(kblocks, row_tile) for this shape, tuning cache first.

    Consults kernels.autotune (env `REPRO_TUNE_CACHE`) under the
    "paged_attn" kernel key and the decode/prefill shape family; a miss —
    or no cache at all — keeps the PR-5 defaults (one block per step, one
    row tile). Values are clamped to the actual geometry so a cache tuned
    on a bigger shape family can never produce an invalid grid.
    """
    from repro.kernels import autotune
    cfg = autotune.lookup("paged_attn",
                          autotune.attn_family(window, c),
                          backend="kernel")
    if autotune.cache_path():
        KERNEL_COUNTERS.tune_lookup("paged_attn", hit=cfg is not None)
    kblocks = 1
    row_tile = None
    if cfg:
        kblocks = max(1, min(int(cfg.get("kblocks", 1) or 1), mb))
        row_tile = cfg.get("row_tile")
        if row_tile:
            row_tile = max(1, min(int(row_tile), cg))
    return kblocks, row_tile


def paged_flash_attention(q, k_pool, v_pool, tables, lens, kv_len, *,
                          interpret: bool | None = None,
                          kblocks: int | None = None,
                          row_tile: int | None = None):
    """Flash-style paged attention: q [B, C, H, dh] × pools [NB, KH, bs, dh]
    through per-slot block tables [B, MB] → [B, C, H, dh].

    lens [B] = tokens already cached per slot BEFORE this step's writes
    (the chunk's base position); kv_len [B] = lens + this step's valid
    writes. GQA rows are folded as C·G so decode (C=1) and chunked prefill
    share one kernel; pools stay in their storage dtype and are upcast
    per-block in VMEM.

    kblocks / row_tile (None → tuning cache, default 1 / single tile)
    control the pipeline shape: each sequential grid step fetches `kblocks`
    logical KV blocks (tables are padded with trash entries to divide), and
    the C·G query rows split into `row_tile`-high parallel tiles (rows are
    padded with dummy queries to divide — their outputs are sliced away).
    """
    interpret = interpret_mode(interpret)
    b, c, h, dh = q.shape
    kh, bs = k_pool.shape[1:3]
    g = h // kh
    mb = tables.shape[1]
    cg = c * g
    if kblocks is None and row_tile is None:
        kblocks, row_tile = _resolve_attn_config(window=mb * bs, c=c,
                                                 mb=mb, cg=cg)
    kblocks = max(1, min(kblocks or 1, mb))
    if row_tile is not None and (row_tile <= 0 or row_tile >= cg):
        row_tile = None
    if mb % kblocks:
        pad = kblocks - mb % kblocks     # pad entries → trash block 0
        tables = jnp.pad(tables, ((0, 0), (0, pad)))
    # [B, C, KH, G, dh] → [B, KH, C·G, dh]: row r = chunk_off·G + g_idx
    q3 = q.reshape(b, c, kh, g, dh).transpose(0, 2, 1, 3, 4) \
          .reshape(b, kh, cg, dh)
    cg_p = cg
    if row_tile is not None and cg % row_tile:
        cg_p = -(-cg // row_tile) * row_tile
        q3 = jnp.pad(q3, ((0, 0), (0, 0), (0, cg_p - cg), (0, 0)))
    out = _paged_attn_call(q3, k_pool, v_pool, tables, lens, kv_len,
                           block_size=bs, g=g, interpret=interpret,
                           kblocks=kblocks, row_tile=row_tile)
    if cg_p != cg:
        out = out[:, :, :cg, :]
    out = out.reshape(b, kh, c, g, dh).transpose(0, 2, 1, 3, 4) \
             .reshape(b, c, h, dh)
    return out.astype(q.dtype)


@register_attn_backend("kernel", pallas=True)
def _kernel_attention(q, k_pool, v_pool, tables, positions, kv_len):
    lens = positions[:, 0].astype(jnp.int32)  # chunk base = first q position
    return paged_flash_attention(q, k_pool, v_pool, tables, lens, kv_len)


# ---------------------------------------------------------------------------
# fused decode write-scatter: paged_write's .at[].set moved into a kernel
# ---------------------------------------------------------------------------
def _fused_write_kernel(wblk_ref, woff_ref, wval_ref, nk_ref, nv_ref,
                        k_ref, v_ref, ko_ref, vo_ref, *, block_size: int):
    """One slot per (sequential) grid step: the slot's target pool block
    arrives via the scalar-prefetched write-block id, the new K/V row is
    blended in at the write offset, and the block is written straight back
    (the pools are input/output aliased, so untouched blocks never move).
    Invalid lanes (write target = the trash block) write their block back
    unmodified — unlike `models.common.paged_write`, the trash block's row
    0 is never clobbered, which only ever differs in never-attended bits.
    """
    b = pl.program_id(0)
    off = woff_ref[b]
    valid = wval_ref[b]
    rows = jax.lax.broadcasted_iota(jnp.int32, (1, 1, block_size, 1), 2)
    sel = (rows == off) & (valid != 0)
    ko_ref[...] = jnp.where(sel, nk_ref[...], k_ref[...])
    vo_ref[...] = jnp.where(sel, nv_ref[...], v_ref[...])


@functools.partial(jax.jit, static_argnames=("interpret",))
def _fused_write_call(k_pool, v_pool, new_k, new_v, wblk, woff, wval, *,
                      interpret: bool):
    nb, kh, bs, dh = k_pool.shape
    b = new_k.shape[0]
    kern = functools.partial(_fused_write_kernel, block_size=bs)
    new_spec = pl.BlockSpec((1, kh, 1, dh),
                            lambda b, t, o, v: (b, 0, 0, 0))
    pool_spec = pl.BlockSpec((1, kh, bs, dh),
                             lambda b, t, o, v: (t[b], 0, 0, 0))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,
        grid=(b,),
        in_specs=[new_spec, new_spec, pool_spec, pool_spec],
        out_specs=[pool_spec, pool_spec],
    )
    return pl.pallas_call(
        kern,
        grid_spec=grid_spec,
        out_shape=[jax.ShapeDtypeStruct(k_pool.shape, k_pool.dtype),
                   jax.ShapeDtypeStruct(v_pool.shape, v_pool.dtype)],
        # pools alias their outputs (operand indices count the 3 scalar-
        # prefetch refs): blocks no grid step visits keep their bytes
        input_output_aliases={5: 0, 6: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(wblk.astype(jnp.int32), woff.astype(jnp.int32),
      wval.astype(jnp.int32),
      # [B, 1, KH, dh] → [B, KH, 1, dh]: the same bytes (C = 1)
      new_k.reshape(b, kh, 1, dh).astype(k_pool.dtype),
      new_v.reshape(b, kh, 1, dh).astype(v_pool.dtype), k_pool, v_pool)


def fused_paged_write(k_pool, v_pool, new_k, new_v, flat_idx, *,
                      interpret: bool | None = None):
    """Kernel-side decode write: scatter each slot's new K/V row (C = 1)
    into its pool block without the host-visible `.at[].set` round trip.

    new_k / new_v [B, 1, KH, dh]; flat_idx [B, 1] flat (block·bs + offset)
    write targets as built by transformer.paged_step — 0 marks an invalid
    lane (paged_write would park it in the trash block; here it is a
    no-op, the only deliberate divergence). Returns the updated pools.

    Prefix-sharing contract (PR 7): flat_idx is derived from the block
    table the HOST passes into the step, and the scheduler copy-on-write
    forks any shared block before stepping (runtime.server._write_plan →
    transformer.cow_copy_block), so by the time this epilogue runs the
    remapped table already points every write at a privately held block —
    the kernel never needs to know about refcounts, and must never be
    handed a table whose write-span blocks are still shared.
    """
    interpret = interpret_mode(interpret)
    bs = k_pool.shape[2]
    fi = flat_idx.reshape(-1).astype(jnp.int32)
    return _fused_write_call(k_pool, v_pool, new_k, new_v,
                             fi // bs, fi % bs, (fi != 0).astype(jnp.int32),
                             interpret=interpret)


# ---------------------------------------------------------------------------
# dispatch (the single entry point models.common calls)
# ---------------------------------------------------------------------------
def _mesh_attn_specs(mesh, kh: int):
    """Head-parallel shard_map specs: KV heads over "model" when divisible
    (the serving head layout), everything else replicated — B is a handful
    of slots and the pool is shared storage. Falls back to fully-replicated
    specs (each shard computes every head redundantly but correctly) when
    the model axis cannot divide KH — the same silent fallback
    sharding.spec_for applies to parameters."""
    heads = None
    if "model" in mesh.axis_names and mesh.shape["model"] > 1 \
            and kh % mesh.shape["model"] == 0:
        heads = "model"
    q_spec = PartitionSpec(None, heads, None, None)
    pool_spec = PartitionSpec(None, heads, None, None)
    return q_spec, pool_spec


def paged_attention(q, k_pool, v_pool, tables, *, positions, kv_len,
                    backend: str = "auto"):
    """Attend q over a paged KV pool through per-slot block tables.

    q [B, C, H, dh]; pools [NB, KH, bs, dh]; tables [B, MB]; positions
    [B, C] absolute query positions (lens + chunk offset, as built by
    transformer.paged_step); kv_len [B]. Returns [B, C, H, dh]. `backend`
    is "auto" | "exact" | "kernel" (see module docstring; models thread
    cfg.attn_backend here). Owns the mesh dispatch: the Pallas backend runs
    per-shard inside sharding.shard_map whenever a mesh is active, heads
    over "model".
    """
    name = choose_attn_backend(backend)
    # trace-time dispatch counter (one count per compiled shape, not per
    # executed step — see telemetry.KernelCounters)
    KERNEL_COUNTERS.count_attn(name)
    spec = get_attn_backend(name)
    mesh = sharding.get_mesh()
    if not (spec.pallas and mesh is not None
            and not sharding.in_shard_context()):
        return spec.fn(q, k_pool, v_pool, tables, positions, kv_len)

    b, c, h, dh = q.shape
    kh = k_pool.shape[1]
    q5 = q.reshape(b, c, kh, h // kh, dh)   # split heads → KH is an axis
    q_spec, pool_spec = _mesh_attn_specs(mesh, kh)
    q5_spec = PartitionSpec(None, None, q_spec[1], None, None)

    def shard_fn(q_l, k_l, v_l, t_l, pos_l, kvl_l):
        q_flat = q_l.reshape(q_l.shape[0], c, -1, dh)
        return spec.fn(q_flat, k_l, v_l, t_l, pos_l, kvl_l).reshape(
            q_l.shape)

    out = sharding.shard_map(
        shard_fn, mesh=mesh,
        in_specs=(q5_spec, pool_spec, pool_spec,
                  PartitionSpec(None, None), PartitionSpec(None, None),
                  PartitionSpec(None)),
        out_specs=q5_spec,
        check_vma=False,
    )(q5, k_pool, v_pool, tables, positions, kv_len)
    return out.reshape(b, c, h, dh)
