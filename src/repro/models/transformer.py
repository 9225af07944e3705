"""Decoder-family transformer: dense GQA LMs, MoE LMs (qwen2/deepseek),
VLM-prefix LMs (internvl2) and enc-dec audio (whisper).

One parameterized implementation so the CIM execution mode, sharding rules,
remat policy, caches and the dry-run lowering path are shared across
architectures. Layer stacks are lax.scan'd over stacked weights (61-layer
512-way SPMD must compile on one CPU core).
"""
from __future__ import annotations

from typing import Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, ShapeConfig
from repro.parallel.sharding import constrain

from . import common, mla, moe
from .common import (attention_apply, attention_init, cross_entropy, dense,
                     dtype_of, embed_init, embed_lookup, mlp_apply, mlp_init,
                     norm, norm_init, unembed)


# ---------------------------------------------------------------------------
# layer init
# ---------------------------------------------------------------------------
def _layer_init(key, cfg: ModelConfig, *, ffn: str, d_model=None) -> dict:
    """One decoder layer. ffn: "dense" | "moe" | "dense_wide" (deepseek)."""
    ks = jax.random.split(key, 4)
    p = {"norm1": norm_init(d_model or cfg.d_model, dtype=dtype_of(cfg),
                            kind=cfg.norm),
         "norm2": norm_init(d_model or cfg.d_model, dtype=dtype_of(cfg),
                            kind=cfg.norm)}
    if cfg.mla is not None:
        p["attn"] = mla.init(ks[0], cfg)
    else:
        p["attn"] = attention_init(ks[0], cfg, d_model=d_model)
    if ffn == "moe":
        p["ffn"] = moe.init(ks[1], cfg)
    elif ffn == "dense_wide":
        p["ffn"] = mlp_init(ks[1], cfg, d_ff=cfg.moe.d_ff_dense)
    else:
        p["ffn"] = mlp_init(ks[1], cfg)
    if cfg.cross_attention:
        p["norm_x"] = norm_init(cfg.d_model, dtype=dtype_of(cfg), kind=cfg.norm)
        p["xattn"] = attention_init(ks[2], cfg)
    return p


def _stack(layers: list[dict]) -> dict:
    return jax.tree.map(lambda *xs: jnp.stack(xs), *layers)


def init(key: jax.Array, cfg: ModelConfig, *, max_seq: int = 0) -> dict:
    ks = jax.random.split(key, 8)
    params: dict = {"tok": embed_init(ks[0], cfg),
                    "final_norm": norm_init(cfg.d_model, dtype=dtype_of(cfg),
                                            kind=cfg.norm)}
    n_dense_wide = cfg.moe.first_dense if cfg.moe else 0
    n_moe = cfg.n_layers - n_dense_wide if cfg.moe else 0

    if n_dense_wide:
        params["dense_layers"] = _stack(
            [_layer_init(jax.random.fold_in(ks[1], i), cfg, ffn="dense_wide")
             for i in range(n_dense_wide)])
    main_ffn = "moe" if cfg.moe else "dense"
    n_main = n_moe if cfg.moe else cfg.n_layers
    params["layers"] = _stack(
        [_layer_init(jax.random.fold_in(ks[2], i), cfg, ffn=main_ffn)
         for i in range(n_main)])

    if cfg.encoder_layers:
        enc_cfg = cfg.replace(cross_attention=False)
        params["enc_layers"] = _stack(
            [_layer_init(jax.random.fold_in(ks[3], i), enc_cfg, ffn="dense")
             for i in range(cfg.encoder_layers)])
        params["enc_norm"] = norm_init(cfg.d_model, dtype=dtype_of(cfg),
                                       kind=cfg.norm)
        params["enc_pos"] = {"pos_embed": _pos_table(ks[4], cfg.encoder_len,
                                                     cfg)}
    if cfg.pos_embed == "learned":
        assert max_seq > 0, "learned positions need max_seq at init"
        params["dec_pos"] = {"pos_embed": _pos_table(ks[5], max_seq, cfg)}

    if cfg.mtp:  # deepseek multi-token prediction: one extra block + proj
        params["mtp"] = {
            "proj": common.dense_init(ks[6], 2 * cfg.d_model, cfg.d_model,
                                      dtype=dtype_of(cfg), name_w="w_proj"),
            "block": _layer_init(ks[7], cfg, ffn="dense_wide" if cfg.moe
                                 else "dense"),
            "norm_h": norm_init(cfg.d_model, dtype=dtype_of(cfg), kind=cfg.norm),
            "norm_e": norm_init(cfg.d_model, dtype=dtype_of(cfg), kind=cfg.norm),
        }
    return params


def _pos_table(key, n: int, cfg: ModelConfig):
    return (jax.random.normal(key, (n, cfg.d_model), jnp.float32)
            * 0.02).astype(dtype_of(cfg))


# ---------------------------------------------------------------------------
# forward
# ---------------------------------------------------------------------------
def _layer_fwd(lp: dict, h: jax.Array, cfg: ModelConfig, *, positions,
               train: bool, causal: bool = True,
               enc_out: Optional[jax.Array] = None,
               rng: Optional[jax.Array] = None):
    if cfg.mla is not None:
        a, _ = mla.apply(lp["attn"], norm(lp["norm1"], h, cfg), cfg,
                         positions=positions, train=train)
    else:
        a, _ = attention_apply(lp["attn"], norm(lp["norm1"], h, cfg), cfg,
                               positions=positions, train=train,
                               causal=causal)
    h = h + a
    if enc_out is not None:
        x, _ = attention_apply(lp["xattn"], norm(lp["norm_x"], h, cfg), cfg,
                               positions=positions, train=train,
                               causal=False, kv_x=enc_out)
        h = h + x
    hn = norm(lp["norm2"], h, cfg)
    if "router" in lp["ffn"]:
        f, aux = moe.apply(lp["ffn"], hn, cfg, train=train, rng=rng)
    else:
        f, aux = mlp_apply(lp["ffn"], hn, cfg, train=train), 0.0
    return h + f, aux


def _run_stack(stacked: dict, h: jax.Array, cfg: ModelConfig, *, positions,
               train: bool, causal: bool = True, enc_out=None, rng=None):
    """lax.scan over stacked layer weights, with optional remat."""
    def body(carry, lp):
        hh, aux_acc = carry
        hh, aux = _layer_fwd(lp, hh, cfg, positions=positions, train=train,
                             causal=causal, enc_out=enc_out, rng=rng)
        return (hh, aux_acc + aux), None

    body_fn = jax.checkpoint(
        body, policy=common.remat_policy(cfg)
    ) if (cfg.remat and train) else body
    (h, aux), _ = common.scan_layers(body_fn, (h, 0.0), stacked,
                                     unroll=not cfg.scan_layers)
    return h, aux


def _encode(params: dict, frames: jax.Array, cfg: ModelConfig, *,
            train: bool) -> jax.Array:
    """Whisper encoder over precomputed (stub) conv-frontend frames."""
    pos = params["enc_pos"]["pos_embed"][: frames.shape[1]]
    h = frames.astype(dtype_of(cfg)) + pos
    positions = jnp.broadcast_to(jnp.arange(frames.shape[1]),
                                 frames.shape[:2])
    h, _ = _run_stack(params["enc_layers"], h, cfg, positions=positions,
                      train=train, causal=False)
    return norm(params["enc_norm"], h, cfg)


def _embed_inputs(params, batch, cfg: ModelConfig, *, offset: int = 0):
    """Token embeddings (+learned positions, +VLM image prefix)."""
    tokens = batch["tokens"]
    x = embed_lookup(params["tok"], tokens, cfg)
    if cfg.n_image_tokens and "image_embeds" in batch:
        img = batch["image_embeds"].astype(x.dtype)
        img = constrain(img, "batch", None, None)
        x = jnp.concatenate([img, x], axis=1)
    b, t = x.shape[:2]
    positions = offset + jnp.broadcast_to(jnp.arange(t), (b, t))
    if cfg.pos_embed == "learned":
        x = x + jax.lax.dynamic_slice_in_dim(params["dec_pos"]["pos_embed"],
                                             offset, t, 0)
    return x, positions


def forward(params: dict, batch: dict, cfg: ModelConfig, *,
            train: bool, rng=None):
    """Full-sequence forward → (hidden [B,T,D], aux_loss, enc_out)."""
    x, positions = _embed_inputs(params, batch, cfg)
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encode(params, batch["frames"], cfg, train=train)
    aux_total = 0.0
    if "dense_layers" in params:
        x, aux = _run_stack(params["dense_layers"], x, cfg,
                            positions=positions, train=train, rng=rng)
        aux_total += aux
    x, aux = _run_stack(params["layers"], x, cfg, positions=positions,
                        train=train, enc_out=enc_out, rng=rng)
    aux_total += aux
    return norm(params["final_norm"], x, cfg), aux_total, enc_out


def train_loss(params: dict, batch: dict, cfg: ModelConfig,
               rng: Optional[jax.Array] = None) -> jax.Array:
    h, aux, _ = forward(params, batch, cfg, train=True, rng=rng)
    labels = batch["labels"]
    if cfg.n_image_tokens and "image_embeds" in batch:
        h = h[:, cfg.n_image_tokens:]  # loss on text positions only
    loss = _lm_loss(params, h, labels, cfg)
    if cfg.mtp:
        loss = loss + cfg.mtp_weight * _mtp_loss(params, h, batch, cfg)
    return loss + 0.01 * aux


def _lm_loss(params, h, labels, cfg: ModelConfig):
    """Next-token CE; with cfg.ce_chunks > 1 the [tokens, vocab] logits are
    produced and consumed one sequence chunk at a time (remat'd), so the
    full tensor never lives in HBM (§Perf A4)."""
    n = cfg.ce_chunks
    t = h.shape[1]
    if n <= 1 or t % n != 0:
        return cross_entropy(unembed(params["tok"], h, cfg, train=True),
                             labels)
    hc = h.reshape(h.shape[0], n, t // n, h.shape[2]).swapaxes(0, 1)
    lc = labels.reshape(labels.shape[0], n, t // n).swapaxes(0, 1)

    @jax.checkpoint
    def chunk_nll(hx, lx):
        logits = unembed(params["tok"], hx, cfg, train=True)
        lse = jax.nn.logsumexp(logits, axis=-1)
        picked = jnp.take_along_axis(logits, lx[..., None], axis=-1)[..., 0]
        return jnp.sum(lse - picked)

    def body(acc, xs):
        hx, lx = xs
        return acc + chunk_nll(hx, lx), None

    total, _ = jax.lax.scan(body, jnp.zeros(()), (hc, lc),
                            unroll=True if not cfg.scan_layers else 1)
    return total / (labels.shape[0] * t)


def _mtp_loss(params, h, batch, cfg: ModelConfig):
    """DeepSeek-V3 MTP: predict token t+2 from (hidden_t ∥ embed(token_{t+1}))
    through one extra transformer block sharing embedding and head."""
    mp = params["mtp"]
    tokens, labels = batch["tokens"], batch["labels"]
    # positions t predicts labels[t+1] = tokens[t+2]
    h_in = norm(mp["norm_h"], h[:, :-1], cfg)
    e_next = norm(mp["norm_e"],
                  embed_lookup(params["tok"], tokens[:, 1:], cfg), cfg)
    merged = dense(mp["proj"], jnp.concatenate([h_in, e_next], -1), cfg,
                   train=True, w="w_proj", b=None)
    b, t = merged.shape[:2]
    positions = jnp.broadcast_to(jnp.arange(t), (b, t))
    h2, _ = _layer_fwd(mp["block"], merged, cfg, positions=positions,
                       train=True)
    logits2 = unembed(params["tok"], h2, cfg, train=True)
    return cross_entropy(logits2, labels[:, 1:])


# ---------------------------------------------------------------------------
# serving: prefill + decode with KV caches
# ---------------------------------------------------------------------------
def init_cache(cfg: ModelConfig, batch: int, max_len: int) -> dict:
    """Abstract cache pytree (zeros); layout matches decode_step."""
    dt = dtype_of(cfg)
    n_wide = cfg.moe.first_dense if cfg.moe else 0
    n_main = cfg.n_layers - n_wide
    if cfg.mla is not None:
        lat = cfg.mla.kv_lora_rank + cfg.mla.qk_rope_head_dim
        mk = lambda L: {"latent": jnp.zeros((L, batch, max_len, lat), dt)}
    else:
        kvd = (batch, max_len, cfg.n_kv_heads, cfg.head_dim)
        mk = lambda L: {"k": jnp.zeros((L,) + kvd, dt),
                        "v": jnp.zeros((L,) + kvd, dt)}
    cache = {"pos": jnp.zeros((), jnp.int32), "layers": mk(n_main)}
    if n_wide:
        cache["dense_layers"] = mk(n_wide)
    if cfg.cross_attention:
        cache["cross"] = {
            "k": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                            cfg.n_kv_heads, cfg.head_dim), dt),
            "v": jnp.zeros((cfg.n_layers, batch, cfg.encoder_len,
                            cfg.n_kv_heads, cfg.head_dim), dt)}
    return cache


def _layer_decode(lp: dict, h: jax.Array, layer_cache: dict,
                  cfg: ModelConfig, *, positions, pos_idx,
                  cross_cache=None):
    if cfg.mla is not None:
        a, new_c = mla.apply(lp["attn"], norm(lp["norm1"], h, cfg), cfg,
                             positions=positions, cache=layer_cache,
                             cache_index=pos_idx)
    else:
        a, new_c = attention_apply(lp["attn"], norm(lp["norm1"], h, cfg), cfg,
                                   positions=positions, cache=layer_cache,
                                   cache_index=pos_idx)
    h = h + a
    if cross_cache is not None:
        x, _ = attention_apply(lp["xattn"], norm(lp["norm_x"], h, cfg), cfg,
                               positions=positions, kv_x=h,  # unused w/ cache
                               cache=cross_cache)
        h = h + x
    hn = norm(lp["norm2"], h, cfg)
    if "router" in lp["ffn"]:
        f, _ = moe.apply(lp["ffn"], hn, cfg, train=False)
    else:
        f = mlp_apply(lp["ffn"], hn, cfg)
    return h + f, new_c


def _decode_stack(stacked, caches, h, cfg, *, positions, pos_idx,
                  cross=None):
    def body(hh, xs):
        if cross is None:
            lp, lc = xs
            xc = None
        else:
            lp, lc, xc = xs
        hh, new_c = _layer_decode(lp, hh, lc, cfg, positions=positions,
                                  pos_idx=pos_idx, cross_cache=xc)
        return hh, new_c

    xs = (stacked, caches) if cross is None else (stacked, caches, cross)
    return common.scan_layers(body, h, xs, unroll=not cfg.scan_layers)


def decode_step(params: dict, tokens: jax.Array, cache: dict,
                cfg: ModelConfig):
    """One decode step: tokens [B,1] → (logits [B,V], updated cache)."""
    pos = cache["pos"]
    x, positions = _embed_inputs(params, {"tokens": tokens}, cfg)
    positions = positions + pos
    if cfg.pos_embed == "learned":  # re-slice at the dynamic position
        x = embed_lookup(params["tok"], tokens, cfg)
        x = x + jax.lax.dynamic_slice_in_dim(
            params["dec_pos"]["pos_embed"], pos, 1, 0)

    new_cache = dict(cache)
    if "dense_layers" in params:
        x, nc = _decode_stack(params["dense_layers"], cache["dense_layers"],
                              x, cfg, positions=positions, pos_idx=pos)
        new_cache["dense_layers"] = nc
    cross = cache.get("cross")
    x, nc = _decode_stack(params["layers"], cache["layers"], x, cfg,
                          positions=positions, pos_idx=pos, cross=cross)
    new_cache["layers"] = nc
    x = norm(params["final_norm"], x, cfg)
    logits = unembed(params["tok"], x[:, 0], cfg)
    new_cache["pos"] = pos + 1
    return logits, new_cache


def prefill(params: dict, batch: dict, cfg: ModelConfig,
            max_len: int | None = None):
    """Process a full prompt; returns (last-token logits, filled cache).

    Implemented as the training forward plus per-layer K/V collection —
    GSPMD-friendly (no sequential decode loop over the prompt).
    """
    x, positions = _embed_inputs(params, batch, cfg)
    b, t = x.shape[:2]
    max_len = max_len or t
    enc_out = None
    if cfg.encoder_layers:
        enc_out = _encode(params, batch["frames"], cfg, train=False)

    def collect(stacked, h):
        def body(hh, lp):
            if cfg.mla is not None:
                hn = norm(lp["norm1"], hh, cfg)
                a, kv = mla.apply(lp["attn"], hn, cfg, positions=positions,
                                  return_cache=True)
            else:
                hn = norm(lp["norm1"], hh, cfg)
                a, kv = attention_apply(lp["attn"], hn, cfg,
                                        positions=positions, causal=True,
                                        cache={})  # request prefill cache
            hh = hh + a
            if enc_out is not None:
                xo, xkv = attention_apply(lp["xattn"],
                                          norm(lp["norm_x"], hh, cfg), cfg,
                                          positions=positions, causal=False,
                                          kv_x=enc_out, cache={})
                hh = hh + xo
                kv = {**kv, "xk": xkv["k"], "xv": xkv["v"]}
            hn2 = norm(lp["norm2"], hh, cfg)
            if "router" in lp["ffn"]:
                f, _ = moe.apply(lp["ffn"], hn2, cfg, train=False)
            else:
                f = mlp_apply(lp["ffn"], hn2, cfg)
            return hh + f, kv

        return common.scan_layers(body, h, stacked,
                                  unroll=not cfg.scan_layers)

    cache: dict = {"pos": jnp.full((), t, jnp.int32)}
    h = x
    if "dense_layers" in params:
        h, kv = collect(params["dense_layers"], h)
        cache["dense_layers"] = _pad_cache(kv, max_len)
    h, kv = collect(params["layers"], h)
    if cfg.cross_attention:
        cache["cross"] = {"k": kv.pop("xk"), "v": kv.pop("xv")}
    cache["layers"] = _pad_cache(kv, max_len)
    h = norm(params["final_norm"], h, cfg)
    logits = unembed(params["tok"], h[:, -1], cfg)
    return logits, cache


def _pad_cache(kv: dict, max_len: int) -> dict:
    def pad(a):  # [L, B, T, ...] → [L, B, max_len, ...]
        pad_t = max_len - a.shape[2]
        if pad_t <= 0:
            return a
        widths = [(0, 0)] * a.ndim
        widths[2] = (0, pad_t)
        return jnp.pad(a, widths)

    return jax.tree.map(pad, kv)


# ---------------------------------------------------------------------------
# serving: paged KV cache (block pool + block tables)
# ---------------------------------------------------------------------------
def supports_paged(cfg: ModelConfig) -> bool:
    """Paged serving covers the GQA transformer archs (dense / MoE / VLM
    text decode). MLA latent caches and whisper cross-attention keep the
    dense slot cache for now (ROADMAP serving section tracks both)."""
    return cfg.mla is None and not cfg.cross_attention


def init_paged_cache(cfg: ModelConfig, num_blocks: int,
                     block_size: int) -> dict:
    """Physical KV block pools [L, NB, KH, bs, dh] (zeros).

    One pool per layer stack; NB includes the trash block (physical id 0).
    Head-major inside a block: one (block, head) tile is [bs, dh], which is
    the TPU-legal KV fetch of the paged-attention kernel.
    Unlike init_cache there is no per-slot batch axis — slots share the pool
    through their block tables, so resident bytes scale with allocated
    blocks, not n_slots × max_len.
    """
    if not supports_paged(cfg):
        raise NotImplementedError(
            f"paged KV serving not implemented for arch {cfg.arch!r} "
            "(MLA latent / cross-attention caches)")
    dt = dtype_of(cfg)
    n_wide = cfg.moe.first_dense if cfg.moe else 0
    n_main = cfg.n_layers - n_wide
    kvd = (num_blocks, cfg.n_kv_heads, block_size, cfg.head_dim)
    mk = lambda L: {"k": jnp.zeros((L,) + kvd, dt),
                    "v": jnp.zeros((L,) + kvd, dt)}
    cache = {"layers": mk(n_main)}
    if n_wide:
        cache["dense_layers"] = mk(n_wide)
    return cache


def cow_copy_block(cache: dict, src: jax.Array, dst: jax.Array) -> dict:
    """Copy one physical block's K/V (every layer) from `src` to `dst`.

    The copy-on-write primitive behind prefix sharing (runtime.server):
    before a lane writes into a block another holder also maps, the
    scheduler acquires a private block and duplicates the shared contents
    here, then remaps the lane's table. src/dst are traced int32 scalars
    so every fork shares one compilation; the server jits this with the
    cache donated, making it an in-place device copy. Pools are
    [L, NB, KH, bs, dh], so the block axis is axis 1 on every leaf.
    """
    return jax.tree.map(lambda a: a.at[:, dst].set(a[:, src]), cache)


def _layer_paged(lp: dict, h: jax.Array, layer_pool: dict, cfg: ModelConfig,
                 *, positions, flat_idx, tables, kv_len):
    a, new_pool = common.paged_attention_apply(
        lp["attn"], norm(lp["norm1"], h, cfg), cfg, positions=positions,
        cache=layer_pool, flat_idx=flat_idx, tables=tables, kv_len=kv_len)
    h = h + a
    hn = norm(lp["norm2"], h, cfg)
    if "router" in lp["ffn"]:
        f, _ = moe.apply(lp["ffn"], hn, cfg, train=False)
    else:
        f = mlp_apply(lp["ffn"], hn, cfg)
    return h + f, new_pool


def _paged_stack(stacked, pools, h, cfg, *, positions, flat_idx, tables,
                 kv_len):
    def body(hh, xs):
        lp, lc = xs
        hh, new_pool = _layer_paged(lp, hh, lc, cfg, positions=positions,
                                    flat_idx=flat_idx, tables=tables,
                                    kv_len=kv_len)
        return hh, new_pool

    return common.scan_layers(body, h, (stacked, pools),
                              unroll=not cfg.scan_layers)


def paged_step(params: dict, tokens: jax.Array, cache: dict,
               tables: jax.Array, lens: jax.Array, valid: jax.Array,
               cfg: ModelConfig, all_logits: bool = False):
    """One unified serving step over the paged pool: prefill chunks and
    decode are the SAME function (decode is the C=1 compilation).

    tokens [B, C] — C=1 for a pure-decode step, the prefill chunk width
    otherwise; a mixed batch runs decode slots as valid=1 lanes inside a
    C-wide call. lens [B] = tokens already in each slot's cache; valid [B]
    = new tokens this step (0 = idle lane). Writes each slot's new K/V at
    its true positions through its block table (masked lanes → the trash
    block), attends per-slot through the attention backend selected by
    cfg.attn_backend (kernels.paged_attention: "exact" window softmax vs
    the Pallas flash "kernel" whose live scores are one [C·G, bs] tile),
    and returns (logits, updated pool). By default logits are [B, V] taken
    at each slot's LAST valid position — prefill lanes only ever need
    their final chunk's last row. `all_logits=True` (a trace-time flag:
    the server jits it as a separate compilation) unembeds EVERY chunk
    position instead, returning [B, C, V] — the speculative-decoding
    verify shape, where one C=K+1 call scores all K drafted tokens plus
    the bonus position. The host scheduler decides whose logits mean
    anything this step (decode slots every step; prefilling slots only on
    their final chunk).
    """
    b, c = tokens.shape
    block_size = jax.tree_util.tree_leaves(cache)[0].shape[3]
    window = tables.shape[1] * block_size
    positions = lens[:, None] + jnp.arange(c)[None, :]          # [B, C]

    x = embed_lookup(params["tok"], tokens, cfg)
    if cfg.pos_embed == "learned":
        x = x + params["dec_pos"]["pos_embed"][
            jnp.clip(positions, 0, params["dec_pos"]["pos_embed"].shape[0] - 1)]

    # write targets: logical position → (physical block, offset); lanes
    # beyond `valid` (and beyond the window) land in the trash block
    pos_w = jnp.minimum(positions, window - 1)
    blk = jnp.take_along_axis(tables, pos_w // block_size, axis=1)
    flat_idx = blk * block_size + pos_w % block_size
    in_valid = jnp.arange(c)[None, :] < valid[:, None]
    flat_idx = jnp.where(in_valid & (positions < window), flat_idx, 0)
    kv_len = lens + valid

    new_cache = dict(cache)
    if "dense_layers" in params:
        x, np_ = _paged_stack(params["dense_layers"], cache["dense_layers"],
                              x, cfg, positions=positions, flat_idx=flat_idx,
                              tables=tables, kv_len=kv_len)
        new_cache["dense_layers"] = np_
    x, np_ = _paged_stack(params["layers"], cache["layers"], x, cfg,
                          positions=positions, flat_idx=flat_idx,
                          tables=tables, kv_len=kv_len)
    new_cache["layers"] = np_
    x = norm(params["final_norm"], x, cfg)
    if all_logits:
        return unembed(params["tok"], x, cfg), new_cache        # [B, C, V]
    last = jnp.maximum(valid - 1, 0)                            # [B]
    h_last = jnp.take_along_axis(
        x, last[:, None, None].astype(jnp.int32), axis=1)[:, 0]
    logits = unembed(params["tok"], h_last, cfg)
    return logits, new_cache


# ---------------------------------------------------------------------------
# dry-run input specs
# ---------------------------------------------------------------------------
def input_specs(cfg: ModelConfig, shape: ShapeConfig) -> dict:
    """ShapeDtypeStruct stand-ins for every model input of this cell."""
    b, s = shape.global_batch, shape.seq_len
    tok = jax.ShapeDtypeStruct((b, s), jnp.int32)
    specs: dict = {}
    if shape.kind == "train":
        t = s - cfg.n_image_tokens if cfg.n_image_tokens else s
        specs = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((b, t), jnp.int32)}
    elif shape.kind == "prefill":
        t = s - cfg.n_image_tokens if cfg.n_image_tokens else s
        specs = {"tokens": jax.ShapeDtypeStruct((b, t), jnp.int32)}
    else:  # decode: one new token against a seq_len-deep cache
        specs = {"tokens": jax.ShapeDtypeStruct((b, 1), jnp.int32)}
    if cfg.n_image_tokens and shape.kind != "decode":
        specs["image_embeds"] = jax.ShapeDtypeStruct(
            (b, cfg.n_image_tokens, cfg.d_model), jnp.bfloat16)
    if cfg.encoder_layers and shape.kind != "decode":
        specs["frames"] = jax.ShapeDtypeStruct(
            (b, cfg.encoder_len, cfg.d_model), jnp.bfloat16)
    return specs
