"""Plain reference of the served model, in straightforward jax.numpy and
one small Pallas kernel for the attention recurrence.

A dense GQA decoder (RMSNorm, RoPE, causal softmax attention, SwiGLU MLP)
whose every matmul runs the bit-parallel CIM arithmetic the configuration
states, written out directly:

  activation codes  x̃ = clip(round(x / s_x) + z, 0, 2^b_a − 1)   static grid
  weight codes      w̃ = clip(round(w / s_w), −2^(b_w−1), 2^(b_w−1)−1) + o
                    s_w = max|w| / (2^(b_w−1) − 1), one per matrix
  per macro group   ADC(Σ_{144 rows} x̃ w̃): round(· / LSB) clipped to the
                    converter's levels, LSB = (2^b_a−1)(2^b_w−1)·144 /
                    (gain · (levels − 1))
  dequantize        (LSB · Σ_groups codes − o Σx̃ − z Σw̃ + o z K) · s_x s_w

Everything between the matmuls is held in the model's dtype (bfloat16);
norms, RoPE and attention compute in float32. It takes the weights the
benchmark made and the grid the benchmark calibrated, and imports nothing
of the program.

The 4-bit activation grid and the converter make this model chaotic at
random weights: a last-bit difference in one activation moves a code, the
code can move an ADC output by one LSB, and that decorrelates the logits
within a layer (PERF.md §6). So the reference keeps the arithmetic of each
stated step exactly, one rounding per operation:

  - the layers run op by op from Python (each operation is its own
    program), so the compiler cannot fuse a product into the following
    sum and round once where the arithmetic rounds twice; only the
    converter stage (codes → Σ codes · LSB) and the attention recurrence
    are compiled as units;
  - attention is the blockwise (flash) recurrence over key blocks of the
    served block size, in float32: per block the scores, the running max,
    exp(s − max), their sum and the weighted values. It is a Pallas kernel
    of its own (written from the recurrence, not taken from the program),
    because only the kernel compiler's own lowering reproduces the order of
    the sum across a block's lanes and the MXU's pass over the weighted
    values; the same recurrence in jax.numpy can part from the served
    kernel in the last bit, which the converter then turns into different
    logits (PERF.md §6);
  - RMSNorm of the positions the server computed in a step of chunks runs
    in that step's shape (`rmsnorm`), since the compiled norm's rounding
    depends on it.

`stats` runs one sequence and returns, per position, the largest logit,
the logit of a given target token and the argmax; `kv_dtype` rounds the
keys and values through a lower precision (the control).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_PART_BYTES = 256 << 20          # f32 bytes of one row block's group partials


class Model:
    """Widths from the configuration file's published keys, the CIM
    arithmetic from its `cim` section, the attention order from its
    `attention` section."""

    def __init__(self, conf: dict):
        m = conf["model"]
        self.d = m["hidden_size"]
        self.heads = m["num_attention_heads"]
        self.kv_heads = m["num_key_value_heads"]
        self.dh = m.get("head_dim") or self.d // self.heads
        self.d_ff = m["intermediate_size"]
        self.vocab = m["vocab_size"]
        self.layers = m["num_hidden_layers"]
        self.theta = float(m["rope_theta"])
        self.eps = float(m["rms_norm_eps"])
        self.tied = bool(m.get("tie_word_embeddings", False))
        c = conf["cim"]
        self.rows = c["macro_rows"]
        self.qx = (1 << c["act_bits"]) - 1
        self.w_max = (1 << (c["weight_bits"] - 1)) - 1
        self.w_min = -(1 << (c["weight_bits"] - 1))
        self.w_off = 1 << (c["weight_bits"] - 1)
        self.levels = c["adc_levels"]
        full = float(self.qx * ((1 << c["weight_bits"]) - 1) * self.rows)
        self.lsb = full / (c["gain"] * (self.levels - 1))
        self.dtype = jnp.dtype(conf["dtype"])
        a = conf["attention"]
        self.attn_block = int(a["block"])
        flags = conf["serving"]["flags"]
        self.chunk = int(flags[flags.index("--prefill-chunk") + 1])
        self._key = (self.d, self.heads, self.kv_heads, self.dh, self.d_ff,
                     self.vocab, self.layers, self.theta, self.eps,
                     self.tied, self.rows, self.qx, self.w_off, self.levels,
                     self.lsb, str(self.dtype), self.attn_block,
                     self.chunk)

    def __eq__(self, other):
        return isinstance(other, Model) and self._key == other._key

    def __hash__(self):
        return hash(self._key)

    def matmul_params(self) -> int:
        """Weights of every matmul per token: layers plus the head."""
        per_layer = self.d * self.dh * (self.heads * 2 + self.kv_heads * 2) \
            + 3 * self.d * self.d_ff
        return per_layer * self.layers + self.d * self.vocab


def _row_block(groups: int, n: int) -> int:
    rb = _PART_BYTES // max(1, groups * n * 4)
    return int(max(8, min(512, 1 << max(0, rb.bit_length() - 1))))


_MATRICES = ("wq", "wk", "wv", "wo", "w_up", "w_gate", "w_down", "head")


def quantize(params, m: Model) -> dict:
    """The weights as the macro stores them: every matrix (stacked layers
    [L, K, N] or the head [K, N]) → {"codes": int8 w̃, "scale": s_w per
    matrix, "sum": Σ_k w̃ (float32)}, computed op by op like any offline
    weight programming step. A tied head is quantized from the embedding's
    transpose."""
    def q(w):
        wf = w.astype(jnp.float32)
        amax = jnp.max(jnp.abs(wf), axis=(-2, -1), keepdims=True)
        s_w = jnp.maximum(amax, 1e-8) / m.w_max
        codes = jnp.clip(jnp.round(wf / s_w), float(m.w_min),
                         float(m.w_max)) + m.w_off
        return {"codes": codes.astype(jnp.int8), "scale": s_w,
                "sum": jnp.sum(codes, axis=-2, keepdims=True)}

    def walk(tree):
        return {k: (q(v) if k in _MATRICES else
                    walk(v) if isinstance(v, dict) else v)
                for k, v in tree.items()}

    out = walk(params)
    if m.tied:
        out["tok"]["head"] = q(params["tok"]["embed"].T)
    return out


@functools.partial(jax.jit, static_argnames=("m",))
def _converter(x, codes, grid, *, m: Model):
    """x f32 [R, K], stored codes [K, N] → (LSB · Σ_groups ADC codes
    [R, N], Σx̃ [R, 1]): the DACs, the 144-row analog MACs and the
    converters."""
    s_x, z = grid[0], grid[1]
    r, k = x.shape
    n = codes.shape[1]
    xq = jnp.clip(jnp.round(x / s_x) + z, 0.0, float(m.qx))
    groups = -(-k // m.rows)
    pad = groups * m.rows - k
    # codes ≤ 15 are exact in bfloat16 and their products sum exactly in
    # float32 (< 2^24), so the MAC below is integer-exact (the CPU backend
    # has no bfloat16 dot with a float32 result; codes stay float32 there)
    cdt = jnp.float32 if jax.default_backend() == "cpu" else jnp.bfloat16
    wg = jnp.pad(codes, ((0, pad), (0, 0))).astype(cdt) \
        .reshape(groups, m.rows, n)
    rb = _row_block(groups, n)
    rp = -(-r // rb) * rb
    xg = jnp.pad(xq, ((0, rp - r), (0, pad))).astype(cdt) \
        .reshape(rp // rb, rb, groups, m.rows)
    inv_lsb = jnp.float32(1.0 / m.lsb)
    lsb = jnp.float32(m.lsb)

    def block(xb):
        part = jnp.einsum("rgk,gkn->rgn", xb, wg,
                          preferred_element_type=jnp.float32)
        code = jnp.clip(jnp.round(part * inv_lsb), 0.0, float(m.levels - 1))
        return jnp.sum(code, axis=1) * lsb

    y = jax.lax.map(block, xg).reshape(rp, n)[:r]
    return y, jnp.sum(xq, axis=-1, keepdims=True)


def cim_matmul(x, w, grid, m: Model):
    """x f32 [R, K] × a stored matrix w (from `quantize`) through the macro
    → f32 [R, N]. The digital correction and the dequantization run op by
    op, each rounded once, after the converter's output has been rounded."""
    y, sum_x = _converter(x, w["codes"], grid, m=m)
    z = grid[1]
    y = y - m.w_off * sum_x
    y = y - z * w["sum"][0]
    y = y + m.w_off * z * x.shape[1]
    return y * grid[0] * w["scale"][0, 0]


def rmsnorm(x, scale, m: Model, chunked=None):
    """RMSNorm of rows x [T, d]. Rows flagged in `chunked` (bool [T]) were
    served in a step of `m.chunk`-token chunks; their norm runs as one
    compiled unit over [T / chunk, chunk, d], the shape that step normalises
    (see `_chunk_norm`). The others run op by op."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, -1, keepdims=True)
    out = (xf * jax.lax.rsqrt(ms + m.eps)
           * scale.astype(jnp.float32)).astype(x.dtype)
    if chunked is None or not np.any(chunked):
        return out
    t, d = x.shape
    wide = _chunk_norm(x.reshape(t // m.chunk, m.chunk, d), scale,
                       eps=m.eps).reshape(t, d)
    return jnp.where(jnp.asarray(chunked)[:, None], wide, out)


@functools.partial(jax.jit, static_argnames=("eps",))
def _chunk_norm(x, scale, *, eps: float):
    """RMSNorm over [N, chunk, d] as one compiled unit. On the TPU its
    normalising factor (mean square, rsqrt) can differ in the last bits
    from the same arithmetic over [T] rows, and a last bit moved can put a
    norm output on a tie of the activation grid (PERF.md §6)."""
    xf = x.astype(jnp.float32)
    ms = jnp.mean(xf * xf, -1, keepdims=True)
    return (xf * jax.lax.rsqrt(ms + eps)
            * scale.astype(jnp.float32)).astype(x.dtype)


@functools.partial(jax.jit, static_argnames=("half", "theta"))
def _frequencies(*, half: int, theta: float):
    """RoPE's inverse frequencies. Compiled with no input, so the compiler
    folds them to a constant exactly as it does inside the served step (the
    device's exp differs from the folding's in the last bit)."""
    return jnp.exp(-jnp.arange(half, dtype=jnp.float32)
                   * (math.log(theta) / half))


def rope(x, pos, m: Model):
    half = m.dh // 2
    freqs = _frequencies(half=half, theta=m.theta)
    ang = pos.astype(jnp.float32)[:, None] * freqs
    cos = jnp.cos(ang)[:, None, :]
    sin = jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           -1).astype(x.dtype)


_ROWS = 32                       # query rows (position × group) per tile


def _attention_kernel(q_ref, k_ref, v_ref, o_ref, m_ref, l_ref, acc_ref, *,
                      bs: int, g: int, scale: float, t: int):
    """One (KV head, row tile) program; a sequential pass over the key
    blocks. Rows are query position × group (row // g is the position)."""
    r = pl.program_id(1)
    j = pl.program_id(2)
    rt = q_ref.shape[1]

    @pl.when(j == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, -1e30)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    # blocks past the tile's last query position hold no key it attends
    last = jnp.minimum((r * rt + rt - 1) // g, t - 1)

    @pl.when(j * bs <= last)
    def _block():
        q = q_ref[0].astype(jnp.float32)
        k = k_ref[0].astype(jnp.float32)
        v = v_ref[0].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        pos_s = j * bs + jax.lax.broadcasted_iota(jnp.int32, (rt, bs), 1)
        pos_q = (r * rt
                 + jax.lax.broadcasted_iota(jnp.int32, (rt, bs), 0)) // g
        mask = (pos_s <= pos_q) & (pos_s < t)
        s = jnp.where(mask, s, -1e30)
        m_prev = m_ref[...]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=-1, keepdims=True))
        p = jnp.exp(s - m_new) * mask.astype(jnp.float32)
        alpha = jnp.exp(m_prev - m_new)
        l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=-1, keepdims=True)
        acc_ref[...] = acc_ref[...] * alpha + jnp.dot(
            p, v, preferred_element_type=jnp.float32)
        m_ref[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)


@functools.partial(jax.jit, static_argnames=("m",))
def attention(q, k, v, *, m: Model):
    """Causal softmax attention, q [T, H, dh] × k, v [T, KH, dh] → [T, H·dh],
    by the blockwise recurrence over key blocks of m.attn_block positions,
    as a Pallas kernel (interpreted on the CPU)."""
    t = q.shape[0]
    g = m.heads // m.kv_heads
    bs = m.attn_block
    nb = -(-t // bs)
    tp = nb * bs
    rows = t * g
    rp = -(-rows // _ROWS) * _ROWS
    q3 = q.astype(jnp.float32).reshape(t, m.kv_heads, g, m.dh) \
        .transpose(1, 0, 2, 3).reshape(m.kv_heads, rows, m.dh)
    q3 = jnp.pad(q3, ((0, 0), (0, rp - rows), (0, 0)))
    k3 = jnp.pad(k, ((0, tp - t), (0, 0), (0, 0))).transpose(1, 0, 2)
    v3 = jnp.pad(v, ((0, tp - t), (0, 0), (0, 0))).transpose(1, 0, 2)
    kern = functools.partial(_attention_kernel, bs=bs, g=g,
                             scale=1.0 / math.sqrt(m.dh), t=t)
    out = pl.pallas_call(
        kern,
        grid=(m.kv_heads, rp // _ROWS, nb),
        in_specs=[pl.BlockSpec((1, _ROWS, m.dh), lambda h, r, j: (h, r, 0)),
                  pl.BlockSpec((1, bs, m.dh), lambda h, r, j: (h, j, 0)),
                  pl.BlockSpec((1, bs, m.dh), lambda h, r, j: (h, j, 0))],
        out_specs=pl.BlockSpec((1, _ROWS, m.dh), lambda h, r, j: (h, r, 0)),
        out_shape=jax.ShapeDtypeStruct((m.kv_heads, rp, m.dh), jnp.float32),
        scratch_shapes=[pltpu.VMEM((_ROWS, 1), jnp.float32),
                        pltpu.VMEM((_ROWS, 1), jnp.float32),
                        pltpu.VMEM((_ROWS, m.dh), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=jax.default_backend() == "cpu",
    )(q3, k3, v3)
    o = out[:, :rows].reshape(m.kv_heads, t, g, m.dh).transpose(1, 0, 2, 3)
    return o.reshape(t, m.heads * m.dh).astype(q.dtype)


def _layer(h, lp, pos, m: Model, *, kv_dtype, mm, chunked=None):
    """One decoder layer; returns the new residual stream and the inputs of
    its matmuls (for the float calibration forward)."""
    t = h.shape[0]
    a, f = lp["attn"], lp["ffn"]
    x = rmsnorm(h, lp["norm1"]["scale"], m, chunked).astype(jnp.float32)
    q = mm(x, a["wq"]).astype(m.dtype).reshape(t, m.heads, m.dh)
    k = mm(x, a["wk"]).astype(m.dtype).reshape(t, m.kv_heads, m.dh)
    v = mm(x, a["wv"]).astype(m.dtype).reshape(t, m.kv_heads, m.dh)
    q, k = rope(q, pos, m), rope(k, pos, m)
    if kv_dtype is not None:
        k = k.astype(kv_dtype).astype(m.dtype)
        v = v.astype(kv_dtype).astype(m.dtype)
    o = attention(q, k, v, m=m)
    h = h + mm(o.astype(jnp.float32), a["wo"]).astype(m.dtype)
    x2 = rmsnorm(h, lp["norm2"]["scale"], m, chunked).astype(jnp.float32)
    up = mm(x2, f["w_up"]).astype(m.dtype)
    gate = mm(x2, f["w_gate"]).astype(m.dtype)
    hh = jax.nn.silu(gate) * up
    return h + mm(hh.astype(jnp.float32), f["w_down"]).astype(m.dtype), \
        (x, o, x2, hh)


def stats(params, tokens, targets, grid, *, m: Model, kv_dtype=None,
          chunked=None, head_rows: int = 64):
    """tokens [T] int32 → numpy per position (max logit, logit of
    targets[t], argmax), all [T]; positions with targets < 0 read logit 0.
    `params` as `quantize` returns them; `chunked` (bool [T]) flags the
    positions served in a step of chunks (see `rmsnorm`). Layer by layer,
    op by op (see the module docstring)."""
    mm = functools.partial(cim_matmul, grid=grid, m=m)
    t = tokens.shape[0]
    pos = jnp.arange(t)
    h = params["tok"]["embed"][tokens]
    for i in range(m.layers):
        lp = jax.tree.map(lambda a, i=i: a[i], params["layers"])
        h, _ = _layer(h, lp, pos, m, kv_dtype=kv_dtype, mm=mm,
                      chunked=chunked)
    h = rmsnorm(h, params["final_norm"]["scale"], m,
                chunked).astype(jnp.float32)
    w = params["tok"]["head"]
    targets = np.asarray(targets)
    mx, picked, arg = [], [], []
    for r0 in range(0, t, head_rows):
        lg = np.asarray(mm(h[r0:r0 + head_rows], w))
        tg = targets[r0:r0 + head_rows]
        mx.append(lg.max(axis=1))
        arg.append(lg.argmax(axis=1))
        picked.append(np.where(tg >= 0, lg[np.arange(len(tg)),
                                           np.maximum(tg, 0)], 0.0))
    return np.concatenate(mx), np.concatenate(picked), np.concatenate(arg)


@functools.partial(jax.jit, static_argnames=("m",))
def act_ranges(params, tokens, *, m: Model):
    """Float forward (no CIM) of one sequence: the smallest and largest
    value entering any matmul, the profile a static grid is set from."""
    pos = jnp.arange(tokens.shape[0])
    h = params["tok"]["embed"][tokens]

    def mm(x, w):
        return jnp.einsum("rk,kn->rn", x.astype(m.dtype), w,
                          preferred_element_type=jnp.float32)

    def body(carry, lp):
        hh, lo, hi = carry
        hh, ins = _layer(hh, lp, pos, m, kv_dtype=None, mm=mm)
        for a in ins:
            af = a.astype(jnp.float32)
            lo = jnp.minimum(lo, jnp.min(af))
            hi = jnp.maximum(hi, jnp.max(af))
        return (hh, lo, hi), None

    init = (h, jnp.float32(jnp.inf), jnp.float32(-jnp.inf))
    (h, lo, hi), _ = jax.lax.scan(body, init, params["layers"])
    hf = rmsnorm(h, params["final_norm"]["scale"], m).astype(jnp.float32)
    return jnp.minimum(lo, jnp.min(hf)), jnp.maximum(hi, jnp.max(hf))


def static_grid(lo: float, hi: float, m: Model) -> tuple[float, float]:
    """(scale, zero point) of a grid covering [min(lo, 0), hi]. The step is
    the next power of two above span / (2^b_a − 1), so that x / s_x is exact
    in every implementation of the division."""
    lo = min(lo, 0.0)
    scale = 2.0 ** math.ceil(math.log2(max(hi - lo, 1e-8) / m.qx))
    zp = float(round(min(max(-lo / scale, 0.0), float(m.qx))))
    return scale, zp
