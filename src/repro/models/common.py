"""Shared model components: CIM-switchable dense layers, norms, RoPE,
chunked (flash-style) attention, MLPs, embeddings and KV caches.

Every weight matmul routes through `dense()` so the paper's analog-CIM
execution mode (core.cim_matmul) is a single config switch for all ten
architectures — the framework-level integration the brief asks for.
"""
from __future__ import annotations

import math
from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.core import quant
from repro.core.cim_matmul import cim_matmul, cim_matmul_ste
from repro.parallel.sharding import constrain

Params = dict


def dtype_of(cfg: ModelConfig):
    return jnp.dtype(cfg.dtype)


def remat_policy(cfg: ModelConfig):
    if cfg.remat_policy == "nothing":
        return jax.checkpoint_policies.nothing_saveable
    return jax.checkpoint_policies.dots_with_no_batch_dims_saveable


def res_axes(cfg: ModelConfig) -> tuple:
    """Sharding of [B, T, D] residual-stream activations: batch over DP axes
    and (with seq_shard) tokens over "model" — Megatron-style sequence
    parallelism; spec_for drops the token axis automatically when T doesn't
    divide (decode T=1)."""
    return ("batch", "seq_tp" if cfg.seq_shard else None, None)


def scan_layers(body, carry, stacked, *, unroll: bool):
    """lax.scan over stacked layer weights, or straight-line unroll.

    Unrolled form exists for the roofline pass: XLA cost_analysis counts a
    while body once regardless of trip count, so analysis cells lower with
    unroll=True (bigger HLO, exact FLOPs/bytes).
    """
    if not unroll:
        return jax.lax.scan(body, carry, stacked)
    length = jax.tree_util.tree_leaves(stacked)[0].shape[0]
    ys = []
    for i in range(length):
        xs = jax.tree.map(lambda a: a[i], stacked)
        carry, y = body(carry, xs)
        ys.append(y)
    if ys and ys[0] is not None:
        ys = jax.tree.map(lambda *z: jnp.stack(z), *ys)
    else:
        ys = None
    return carry, ys


# ---------------------------------------------------------------------------
# initializers
# ---------------------------------------------------------------------------
def dense_init(key, d_in: int, d_out: int, *, dtype, bias: bool = False,
               scale: float | None = None, name_w: str = "w",
               name_b: str = "b") -> Params:
    scale = scale if scale is not None else 1.0 / math.sqrt(d_in)
    p = {name_w: (jax.random.normal(key, (d_in, d_out), jnp.float32)
                  * scale).astype(dtype)}
    if bias:
        p[name_b] = jnp.zeros((d_out,), dtype)
    return p


def norm_init(d: int, *, dtype, kind: str) -> Params:
    p = {"scale": jnp.ones((d,), dtype)}
    if kind == "layernorm":
        p["bias"] = jnp.zeros((d,), dtype)
    return p


# ---------------------------------------------------------------------------
# primitive layers
# ---------------------------------------------------------------------------
def dense(p: Params, x: jax.Array, cfg: ModelConfig, *, train: bool = False,
          w: str = "w", b: str | None = "b") -> jax.Array:
    """y = x @ W (+bias) — on the simulated PICO-RAM macro when cfg.cim.enabled.

    CIM runs in f32 (integer-code arithmetic); the float path runs in the
    model compute dtype. Output is cast back to the compute dtype.

    The CIM branches run inside a `quant.act_site(w)` scope: the weight name
    (layer-index-free by construction — layers share names) is the call-site
    identity the calibration profile records and per-site precision
    overrides (CIMConfig.site_overrides) resolve against.
    """
    if cfg.cim.enabled and (w + "_q") in p:
        # serving path: offline-quantized stored codes — int8 containers or
        # nibble-packed uint8 (1/4 the bf16 HBM bytes); the execution
        # engine (core.engine) dispatches either format to its backend.
        # w_scale is per-matrix or per-channel ([..., 1, M]) transparently;
        # cfg.cim.noise_seed routes NOISY/FULL evals to the fused
        # stochastic kernel with seeded-reproducible draws.
        from repro.core.cim_matmul import cim_matmul_prequant
        with quant.act_site(w):
            y = cim_matmul_prequant(x.astype(jnp.float32), p[w + "_q"],
                                    p[w + "_scale"], cfg.cim)
        y = y.astype(dtype_of(cfg))
    elif cfg.cim.enabled:
        fn = cim_matmul_ste if train else cim_matmul
        with quant.act_site(w):
            y = fn(x.astype(jnp.float32), p[w].astype(jnp.float32), cfg.cim)
        y = y.astype(dtype_of(cfg))
    else:
        y = jnp.einsum("...k,km->...m", x, p[w])
    if b is not None and b in p:
        y = y + p[b]
    return y


def _rs_applicable(cfg: ModelConfig, x: jax.Array) -> bool:
    from repro.parallel import sharding as _sh
    mesh = _sh.get_mesh()
    if not (cfg.tp_reduce_scatter and not cfg.cim.enabled
            and mesh is not None and "model" in mesh.axis_names
            and x.ndim == 3
            and x.shape[1] % mesh.shape["model"] == 0
            and x.shape[2] % mesh.shape["model"] == 0):
        return False
    baxes = _sh.resolve("batch") or ()
    bsize = math.prod(mesh.shape[a] for a in baxes) if baxes else 1
    return x.shape[0] % max(bsize, 1) == 0


def dense_rs(p: Params, x: jax.Array, cfg: ModelConfig, *, w: str,
             b: str | None = None) -> jax.Array:
    """TP output projection with an explicit reduce-scatter epilogue.

    x [B, T, in] with `in` sharded over "model" (heads / ffn hidden);
    returns [B, T, out] with T sharded over "model" (the SP layout the next
    norm runs in). GSPMD lowers the same computation as all-reduce (+implicit
    reshard) = 2× the wire bytes; psum_scatter is the Megatron-SP schedule.
    """
    from jax.sharding import PartitionSpec as P
    from repro.parallel import sharding as _sh
    mesh = _sh.get_mesh()
    batch_axes = _sh.resolve("batch")
    weight = p[w]

    fsdp = _sh.resolve("fsdp") is not None \
        and "data" in mesh.axis_names and mesh.shape["data"] > 1 \
        and weight.shape[1] % mesh.shape["data"] == 0

    def fn(x_l, w_l):
        if fsdp:
            w_l = jax.lax.all_gather(w_l, "data", axis=1, tiled=True)
        part = jnp.einsum("btk,km->btm", x_l, w_l)
        return jax.lax.psum_scatter(part, "model", scatter_dimension=1,
                                    tiled=True)

    w_spec = P("model", "data" if fsdp else None)
    y = _sh.shard_map(
        fn, mesh=mesh,
        in_specs=(P(batch_axes, None, "model"), w_spec),
        out_specs=P(batch_axes, "model", None),
        check_vma=False,
    )(x, weight)
    if b is not None and b in p:
        y = y + p[b]
    return y


def norm(p: Params, x: jax.Array, cfg: ModelConfig) -> jax.Array:
    xf = x.astype(jnp.float32)
    if cfg.norm == "layernorm":
        mu = jnp.mean(xf, -1, keepdims=True)
        var = jnp.var(xf, -1, keepdims=True)
        y = (xf - mu) * jax.lax.rsqrt(var + cfg.norm_eps)
        y = y * p["scale"].astype(jnp.float32) + p["bias"].astype(jnp.float32)
    else:  # rmsnorm
        ms = jnp.mean(xf * xf, -1, keepdims=True)
        y = xf * jax.lax.rsqrt(ms + cfg.norm_eps) * p["scale"].astype(jnp.float32)
    return y.astype(x.dtype)


def rope(x: jax.Array, positions: jax.Array, theta: float,
         rope_dims: int) -> jax.Array:
    """Rotary embedding on the leading `rope_dims` of the head dim.

    x: [B, T, H, dh]; positions: [B, T] absolute positions.
    """
    if rope_dims <= 0:
        return x
    half = rope_dims // 2
    freqs = jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                    * (math.log(theta) / half))
    ang = positions[..., None].astype(jnp.float32) * freqs  # [B, T, half]
    cos = jnp.cos(ang)[:, :, None, :]
    sin = jnp.sin(ang)[:, :, None, :]
    xr, xpass = x[..., :rope_dims], x[..., rope_dims:]
    x1, x2 = xr[..., :half], xr[..., half:]
    rot = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)
    return jnp.concatenate([rot.astype(x.dtype), xpass], -1)


# ---------------------------------------------------------------------------
# chunked (flash-style) attention — pure JAX, O(chunk²) live memory
# ---------------------------------------------------------------------------
def _attn_block(q, k, v, mask, scale):
    """One (q-chunk × kv-chunk) block. q:[B,Cq,KH,G,dh] k/v:[B,Ckv,KH,dh]."""
    s = jnp.einsum("bqkgd,bckd->bqkgc", q, k,
                   preferred_element_type=jnp.float32) * scale
    s = jnp.where(mask[:, :, None, None, :], s, -1e30)
    m = jnp.max(s, axis=-1)
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bqkgc,bckd->bqkgd", p.astype(v.dtype), v,
                   preferred_element_type=jnp.float32)
    return m, l, o


def chunked_attention(q: jax.Array, k: jax.Array, v: jax.Array, *,
                      causal: bool, chunk: int,
                      q_offset: jax.Array | int = 0,
                      kv_valid: jax.Array | int | None = None,
                      triangular_max: int = 8,
                      unroll: bool = False) -> jax.Array:
    """Online-softmax attention: q [B,Tq,H,dh] × k,v [B,Tk,KH,dh] → [B,Tq,H,dh].

    GQA folded as H = KH × G. Scans kv chunks (and q chunks when Tq is
    large); when the q-chunk count is small and causal, unrolls a triangular
    loop so no fully-masked block is ever computed (exact-FLOPs training).
    """
    b, tq, h, dh = q.shape
    _, tk, kh, _ = k.shape
    g = h // kh
    scale = 1.0 / math.sqrt(dh)
    ckv = min(chunk, tk)
    cq = min(chunk, tq)
    pad_kv = (-tk) % ckv
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
    pad_q = (-tq) % cq
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
    nkv = (tk + pad_kv) // ckv
    nq = (tq + pad_q) // cq
    kv_valid = tk if kv_valid is None else kv_valid

    qs = q.reshape(b, nq, cq, kh, g, dh)
    ks = k.reshape(b, nkv, ckv, kh, dh)
    vs = v.reshape(b, nkv, ckv, kh, dh)
    q_idx_base = jnp.asarray(q_offset) + jnp.arange(cq)

    def kv_scan(qi_abs, q_blk, j_lo, j_hi):
        """Online softmax over kv chunks j ∈ [j_lo, j_hi)."""
        def body(carry, j):
            m_acc, l_acc, o_acc = carry
            kj = j * ckv + jnp.arange(ckv)
            mask = kj[None, :] < jnp.minimum(
                jnp.asarray(kv_valid),
                (qi_abs[:, None] + 1) if causal else jnp.iinfo(jnp.int32).max)
            mask = jnp.broadcast_to(mask[None], (b, cq, ckv))
            m, l, o = _attn_block(q_blk, ks[:, j], vs[:, j], mask, scale)
            m_new = jnp.maximum(m_acc, m)
            a_old = jnp.exp(m_acc - m_new)
            a_new = jnp.exp(m - m_new)
            return (m_new, l_acc * a_old + l * a_new,
                    o_acc * a_old[..., None] + o * a_new[..., None]), None

        init = (jnp.full((b, cq, kh, g), -jnp.inf, jnp.float32),
                jnp.zeros((b, cq, kh, g), jnp.float32),
                jnp.zeros((b, cq, kh, g, dh), jnp.float32))
        (m_f, l_f, o_f), _ = jax.lax.scan(body, init, jnp.arange(j_lo, j_hi),
                                          unroll=True if unroll else 1)
        return o_f / jnp.maximum(l_f, 1e-30)[..., None]

    if causal and nq <= triangular_max and isinstance(q_offset, int) \
            and q_offset == 0 and cq % ckv == 0:
        # Triangular unroll: q chunk i only visits kv chunks covering [0, i·cq+cq)
        outs = []
        for i in range(nq):
            qi_abs = i * cq + q_idx_base
            j_hi = (i + 1) * cq // ckv
            outs.append(kv_scan(qi_abs, qs[:, i], 0, j_hi))
        out = jnp.stack(outs, 1)
    else:
        def q_body(_, i):
            qi_abs = i * cq + q_idx_base
            return None, kv_scan(qi_abs, qs[:, i], 0, nkv)
        _, out = jax.lax.scan(q_body, None, jnp.arange(nq),
                              unroll=True if unroll else 1)
        out = jnp.moveaxis(out, 0, 1)  # [B, nq, cq, KH, G, dh]

    out = out.reshape(b, nq * cq, h, dh)[:, :tq]
    return out.astype(q.dtype)


def decode_attention(q: jax.Array, k_cache: jax.Array, v_cache: jax.Array,
                     kv_len: jax.Array) -> jax.Array:
    """Single-token attention over a (possibly sequence-sharded) KV cache.

    q [B,1,H,dh] × caches [B,S,KH,dh] → [B,1,H,dh]. Full-S einsum (no scan):
    GSPMD partitions the S reduction across the "seq" axes, turning the
    softmax into two tiny all-reduces — the production long-context layout.
    """
    b, _, h, dh = q.shape
    _, s, kh, _ = k_cache.shape
    g = h // kh
    qg = q.reshape(b, kh, g, dh)
    scores = jnp.einsum("bkgd,bskd->bkgs", qg, k_cache,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    mask = jnp.arange(s)[None, None, None, :] < kv_len
    scores = jnp.where(mask, scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bkgs,bskd->bkgd", p.astype(v_cache.dtype), v_cache,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, 1, h, dh).astype(q.dtype)


# ---------------------------------------------------------------------------
# paged KV cache: block-pool scatter/gather + windowed attention
# ---------------------------------------------------------------------------
def paged_write(pool: jax.Array, new: jax.Array,
                flat_idx: jax.Array) -> jax.Array:
    """Scatter per-token K or V rows into a block pool.

    pool [NB, KH, bs, dh]; new [B, C, KH, dh]; flat_idx [B, C] indexes the
    flattened (NB·bs) token-slot space (block · bs + offset). Masked lanes
    arrive pre-pointed at the trash block (flat index 0..bs-1) by the
    caller, so no separate mask is needed here — duplicate trash writes
    land in storage that is never read with non-zero attention weight.
    """
    bs = pool.shape[2]
    fi = flat_idx.reshape(-1)
    return pool.at[fi // bs, :, fi % bs].set(
        new.reshape(-1, *new.shape[2:]).astype(pool.dtype))


def paged_gather(pool: jax.Array, tables: jax.Array) -> jax.Array:
    """Gather each slot's window from the block pool.

    pool [NB, KH, bs, dh]; tables [B, MB] physical block ids. Returns the
    contiguous per-slot view [B, MB·bs, KH, dh] — the same window shape the
    dense slot cache gave decode_attention, so the per-position math (and,
    for decode, the bits) match the unpaged path. Unallocated table entries
    point at the trash block; those positions sit at >= the slot's length
    and are masked before any softmax.
    """
    b, mb = tables.shape
    _, kh, bs, dh = pool.shape
    win = pool[tables].swapaxes(2, 3)        # [B, MB, bs, KH, dh]
    return win.reshape(b, mb * bs, kh, dh)


def paged_prefill_attention(q: jax.Array, k_win: jax.Array, v_win: jax.Array,
                            positions: jax.Array,
                            kv_len: jax.Array) -> jax.Array:
    """Causal attention of a prompt chunk against its gathered window.

    q [B,C,H,dh] × k/v windows [B,W,KH,dh] → [B,C,H,dh]; positions [B,C] is
    each query's absolute position (lens + chunk offset), kv_len [B] the
    tokens valid in the window INCLUDING this chunk's writes. Exact (one-
    pass) softmax over the full window rather than the online-softmax of
    chunked_attention: the result is then independent of how the prompt was
    chunked — the invariance the chunked-prefill equivalence tests pin —
    and decode (C=1) keeps using decode_attention so its bits match the
    dense-cache path. W is one request's max context, so this path
    materializes a [B,C,KH,G,W] score tensor — it is the "exact" entry of
    the attention-backend registry (kernels.paged_attention); the "kernel"
    backend is the Pallas flash path whose live scores are one [C·G, bs]
    tile (the TPU-scale serving configuration).
    """
    b, cq, h, dh = q.shape
    w = k_win.shape[1]
    kh = k_win.shape[2]
    g = h // kh
    qg = q.reshape(b, cq, kh, g, dh)
    scores = jnp.einsum("bqkgd,bskd->bqkgs", qg, k_win,
                        preferred_element_type=jnp.float32)
    scores = scores / math.sqrt(dh)
    pos_s = jnp.arange(w)[None, None, :]
    mask = (pos_s <= positions[:, :, None]) & (pos_s < kv_len[:, None, None])
    scores = jnp.where(mask[:, :, None, None, :], scores, -1e30)
    p = jax.nn.softmax(scores, axis=-1)
    o = jnp.einsum("bqkgs,bskd->bqkgd", p.astype(v_win.dtype), v_win,
                   preferred_element_type=jnp.float32)
    return o.reshape(b, cq, h, dh).astype(q.dtype)


def paged_attention_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                          positions: jax.Array, cache: dict,
                          flat_idx: jax.Array, tables: jax.Array,
                          kv_len: jax.Array):
    """Self-attention over a paged KV pool — the unified prefill/decode step.

    x [B, C, D] (C = 1 for decode, the prefill chunk width otherwise);
    cache {"k": [NB, KH, bs, dh], "v": ...} is ONE layer's physical pool.
    Projects and RoPEs this step's tokens at their true per-slot positions,
    scatters them into the pool at flat_idx (masked lanes → trash block),
    and attends with per-slot lengths through the attention-backend
    registry (kernels.paged_attention, selected by cfg.attn_backend):
    "exact" gathers the window and runs the one-pass softmax, "kernel" is
    the Pallas flash path that consumes the pool + tables directly. On the
    kernel path, decode (C = 1) also scatters this step's K/V rows through
    the fused Pallas write kernel instead of the host-visible `.at[].set`
    (bit-identical pools outside the never-attended trash block).
    Returns (y, updated layer pool).
    """
    from repro.kernels.paged_attention import (paged_attention,
                                               choose_attn_backend,
                                               get_attn_backend,
                                               fused_paged_write)
    from repro.parallel import sharding
    b, c, _ = x.shape
    dh = cfg.head_dim
    q = dense(p, x, cfg, w="wq", b="bq").reshape(b, c, cfg.n_heads, dh)
    q = constrain(q, "batch", None, "tp", None)
    k1 = dense(p, x, cfg, w="wk", b="bk").reshape(b, c, cfg.n_kv_heads, dh)
    v1 = dense(p, x, cfg, w="wv", b="bv").reshape(b, c, cfg.n_kv_heads, dh)
    if cfg.pos_embed == "rope":
        q = rope(q, positions, cfg.rope_theta, _rope_dims(cfg))
        k1 = rope(k1, positions, cfg.rope_theta, _rope_dims(cfg))
    fused = (c == 1
             and get_attn_backend(choose_attn_backend(cfg.attn_backend)).pallas
             and sharding.get_mesh() is None
             and not sharding.in_shard_context())
    if fused:
        k_pool, v_pool = fused_paged_write(cache["k"], cache["v"], k1, v1,
                                           flat_idx)
    else:
        k_pool = paged_write(cache["k"], k1, flat_idx)
        v_pool = paged_write(cache["v"], v1, flat_idx)
    o = paged_attention(q, k_pool, v_pool, tables, positions=positions,
                        kv_len=kv_len, backend=cfg.attn_backend)
    o = o.reshape(b, c, cfg.n_heads * dh)
    o = constrain(o, "batch", None, "tp")
    y = dense(p, o, cfg, w="wo", b="bo")
    return constrain(y, *res_axes(cfg)), {"k": k_pool, "v": v_pool}


# ---------------------------------------------------------------------------
# attention layer (projections + cache plumbing)
# ---------------------------------------------------------------------------
def attention_init(key, cfg: ModelConfig, *, d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    dh = cfg.head_dim
    ks = jax.random.split(key, 4)
    dt = dtype_of(cfg)
    p = {}
    p.update(dense_init(ks[0], d, cfg.n_heads * dh, dtype=dt,
                        bias=cfg.qkv_bias, name_w="wq", name_b="bq"))
    p.update(dense_init(ks[1], d, cfg.n_kv_heads * dh, dtype=dt,
                        bias=cfg.qkv_bias, name_w="wk", name_b="bk"))
    p.update(dense_init(ks[2], d, cfg.n_kv_heads * dh, dtype=dt,
                        bias=cfg.qkv_bias, name_w="wv", name_b="bv"))
    p.update(dense_init(ks[3], cfg.n_heads * dh, d, dtype=dt,
                        scale=1.0 / math.sqrt(cfg.n_heads * dh * 2 * cfg.n_layers),
                        name_w="wo", name_b="bo"))
    return p


def attention_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
                    positions: jax.Array, train: bool = False,
                    causal: bool = True,
                    kv_x: jax.Array | None = None,
                    cache: Optional[dict] = None,
                    cache_index: jax.Array | int = 0):
    """Self/cross attention. Returns (y, new_kv_cache_entries | None).

    cache: {"k": [B,S,KH,dh], "v": ...} — decode writes the new token at
    cache_index and attends over the first cache_index+1 entries.
    """
    b, t, _ = x.shape
    dh = cfg.head_dim
    src = x if kv_x is None else kv_x
    q = dense(p, x, cfg, train=train, w="wq", b="bq")
    q = q.reshape(b, t, cfg.n_heads, dh)
    q = constrain(q, "batch", None, "tp", None)
    if cfg.pos_embed == "rope" and kv_x is None:
        q = rope(q, positions, cfg.rope_theta, _rope_dims(cfg))

    new_cache = None
    if cache is not None and kv_x is None and t == 1:
        # decode: project current token, write into cache
        k1 = dense(p, src, cfg, train=train, w="wk", b="bk")
        v1 = dense(p, src, cfg, train=train, w="wv", b="bv")
        k1 = k1.reshape(b, 1, cfg.n_kv_heads, dh)
        v1 = v1.reshape(b, 1, cfg.n_kv_heads, dh)
        if cfg.pos_embed == "rope":
            k1 = rope(k1, positions, cfg.rope_theta, _rope_dims(cfg))
        k_cache = jax.lax.dynamic_update_slice(
            cache["k"], k_cache_dtype(k1, cache), (0, cache_index, 0, 0))
        v_cache = jax.lax.dynamic_update_slice(
            cache["v"], k_cache_dtype(v1, cache), (0, cache_index, 0, 0))
        k_cache = constrain(k_cache, "batch", "seq_tp", None, None)
        v_cache = constrain(v_cache, "batch", "seq_tp", None, None)
        o = decode_attention(q, k_cache, v_cache,
                             jnp.asarray(cache_index) + 1)
        new_cache = {"k": k_cache, "v": v_cache}
    elif cache is not None and kv_x is not None and "k" in cache:
        # cross-attention decode: cache holds precomputed encoder K/V
        o = decode_attention(q, cache["k"], cache["v"], cache["k"].shape[1])
        new_cache = cache
    else:
        k = dense(p, src, cfg, train=train, w="wk", b="bk")
        v = dense(p, src, cfg, train=train, w="wv", b="bv")
        k = k.reshape(b, src.shape[1], cfg.n_kv_heads, dh)
        v = v.reshape(b, src.shape[1], cfg.n_kv_heads, dh)
        if cfg.pos_embed == "rope" and kv_x is None:
            k = rope(k, positions, cfg.rope_theta, _rope_dims(cfg))
        k = constrain(k, "batch", None, "tp", None)
        v = constrain(v, "batch", None, "tp", None)
        o = chunked_attention(q, k, v, causal=causal and kv_x is None,
                              chunk=cfg.attn_chunk,
                              triangular_max=cfg.attn_triangular_max,
                              unroll=not cfg.scan_layers)
        if cache is not None:  # prefill: hand back the filled cache
            new_cache = {"k": k, "v": v}

    o = o.reshape(b, t, cfg.n_heads * dh)
    o = constrain(o, "batch", None, "tp")
    if _rs_applicable(cfg, o):
        y = dense_rs(p, o, cfg, w="wo", b="bo")
    else:
        y = dense(p, o, cfg, train=train, w="wo", b="bo")
    return constrain(y, *res_axes(cfg)), new_cache


def k_cache_dtype(x, cache):
    return x.astype(cache["k"].dtype)


def _rope_dims(cfg: ModelConfig) -> int:
    d = int(cfg.head_dim * cfg.rope_pct)
    return d - (d % 2)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------
def mlp_init(key, cfg: ModelConfig, *, d_ff: int | None = None,
             d_model: int | None = None) -> Params:
    d = d_model or cfg.d_model
    f = d_ff or cfg.d_ff
    dt = dtype_of(cfg)
    ks = jax.random.split(key, 3)
    p = {}
    if cfg.mlp == "swiglu":
        p.update(dense_init(ks[0], d, f, dtype=dt, name_w="w_gate"))
    p.update(dense_init(ks[1], d, f, dtype=dt, name_w="w_up"))
    p.update(dense_init(ks[2], f, d, dtype=dt,
                        scale=1.0 / math.sqrt(f * 2 * cfg.n_layers),
                        name_w="w_down"))
    return p


def mlp_apply(p: Params, x: jax.Array, cfg: ModelConfig, *,
              train: bool = False) -> jax.Array:
    up = dense(p, x, cfg, train=train, w="w_up", b=None)
    up = constrain(up, "batch", None, "tp")
    if cfg.mlp == "swiglu":
        gate = dense(p, x, cfg, train=train, w="w_gate", b=None)
        h = jax.nn.silu(gate) * up
    else:
        h = jax.nn.gelu(up)
    if _rs_applicable(cfg, h):
        y = dense_rs(p, h, cfg, w="w_down")
    else:
        y = dense(p, h, cfg, train=train, w="w_down", b=None)
    return constrain(y, *res_axes(cfg))


# ---------------------------------------------------------------------------
# embedding / unembedding / loss
# ---------------------------------------------------------------------------
def embed_init(key, cfg: ModelConfig) -> Params:
    dt = dtype_of(cfg)
    p = {"embed": (jax.random.normal(key, (cfg.vocab, cfg.d_model),
                                     jnp.float32) * 0.02).astype(dt)}
    if not cfg.tie_embeddings:
        p["head"] = (jax.random.normal(jax.random.fold_in(key, 1),
                                       (cfg.d_model, cfg.vocab), jnp.float32)
                     / math.sqrt(cfg.d_model)).astype(dt)
    return p


def embed_lookup(p: Params, tokens: jax.Array, cfg: ModelConfig) -> jax.Array:
    # The stored table is [V/"tp", D/"fsdp"] (PARAM_RULES): gathering from an
    # operand sharded on the collapsed slice dim, into an output that must
    # land batch-sharded, makes the SPMD partitioner fall back to involuntary
    # full rematerialization. Reshard first into a gather-friendly layout:
    # batch-shard the token ids and move the table's model split onto the
    # offset dim ([V, D/"tp"] — "tp" is disjoint from the batch axes, and
    # offset-dim sharding passes straight through a gather). Each device then
    # gathers only its own batch rows, and the output reshards to res_axes
    # with one small activation all-gather instead of a table remat.
    table = constrain(p["embed"], None, "tp")
    x = table[constrain(tokens, "batch", None)]
    return constrain(x, *res_axes(cfg))


def unembed(p: Params, h: jax.Array, cfg: ModelConfig, *,
            train: bool = False) -> jax.Array:
    if cfg.cim.enabled and "head_q" in p:
        from repro.core.cim_matmul import cim_matmul_prequant
        with quant.act_site("head"):
            logits = cim_matmul_prequant(h.astype(jnp.float32), p["head_q"],
                                         p["head_scale"], cfg.cim)
    else:
        w = p["embed"].T if cfg.tie_embeddings else p.get("head")
        if cfg.cim.enabled:
            fn = cim_matmul_ste if train else cim_matmul
            with quant.act_site("head"):
                logits = fn(h.astype(jnp.float32), w.astype(jnp.float32),
                            cfg.cim)
        else:
            logits = jnp.einsum("...d,dv->...v", h, w)
    logits = logits.astype(jnp.float32)
    axes = ("batch",) + (None,) * (logits.ndim - 2) + ("tp",)
    return constrain(logits, *axes)


def cross_entropy(logits: jax.Array, labels: jax.Array,
                  mask: jax.Array | None = None) -> jax.Array:
    """Token-mean CE. logits [.., V] f32, labels [..] int32."""
    lse = jax.nn.logsumexp(logits, axis=-1)
    picked = jnp.take_along_axis(logits, labels[..., None], axis=-1)[..., 0]
    nll = lse - picked
    if mask is not None:
        return jnp.sum(nll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return jnp.mean(nll)
