"""Fused bit-parallel CIM MVM Pallas TPU kernel.

TPU adaptation of the paper's "in-situ" insight (§III-A): PICO-RAM never
moves analog partials off the local MOM capacitors between MAC, shift-and-add
and ADC sampling. The TPU analogue: never spill pre-ADC partial sums to HBM.
Each grid step along the reduction axis processes exactly one N=144-row macro
group on the MXU and applies the ADC transfer (clip + round to the 8.5-bit
grid with VTC gain) in VMEM registers before accumulating into the output
block — the digital partial-sum accumulation of §II-A.

Layout choices (TPU v5e target):
  * grid = (M/bm, N/bn, G): the two output axes are parallel, the group axis
    is sequential ("arbitrary") and innermost so the f32 output block stays
    resident in VMEM across all G groups (revisiting it per group would
    round-trip HBM — the exact failure the paper's in-situ design avoids).
  * The reduction block equals the macro depth n_rows = 144, which is not a
    multiple of the 128-lane tile. So activations arrive GROUP-MAJOR,
    x [G, M, 144] with block (1, bm, 144): the block's last dim then equals
    the array's, which Mosaic accepts. Weights keep their [K, N] layout with
    a (144, bn) block (144 rows are a whole number of sublane tiles). The
    physical group size stays 144 rather than rounding to 128, so the
    simulated numerics are bit-faithful to the macro (padding rows hold
    zero codes = unselected SRAM rows).
  * Packed weights [K/2, N] u8 hold rows 2i / 2i+1 in the low / high nibble
    of byte row i. Instead of interleaving the nibbles back into rows in
    VMEM, the activations arrive split by parity, x [G, 2, M, 72], and the
    group's MAC is x_even·lo + x_odd·hi — two exact integer dots.
  * The output block accumulates integer ADC codes (≤ G·(levels-1), exact
    in f32) and is scaled by the LSB once, after the last group. Integer
    sums do not depend on their order, so the kernel is bit-identical to
    `kernels/ref.py` however either one orders the group sum.
  * bm/bn default to 128×128 MXU-aligned output tiles; VMEM footprint per
    step ≈ bm·144·4 + 144·bn·4 + bm·bn·4 ≈ 213 KB ≪ 16 MB, leaving room for
    the pipeline's double buffering.

Deterministic (SimLevel.IDEAL) and stochastic (NOISY/FULL) variants share
the grid/layout; the stochastic kernels additionally draw the TD-ADC's
thermal-noise sample per conversion IN VMEM — mirroring the dual-threshold
TD-ADC, which samples its comparator noise independently at every
conversion — so QAT noise studies run at fused-kernel throughput instead of
falling back to the einsum/scan jnp paths.

PRNG choice: a counter-based SplitMix32/murmur3-style hash over
(seed, row, col, group) evaluated with plain uint32 vector ops. The
hardware `pltpu.prng_seed`/`prng_random_bits` primitives have no CPU
interpret-mode lowering, and their draws would differ between compiled and
interpret mode anyway. The counter construction gives bit-identical output
on TPU and in CI's interpret mode, and makes every conversion's draw a pure
function of (noise_seed, output coordinate, group) — reproducible per seed
by construction. Gaussians come from the Irwin–Hall sum of 12 uniforms,
each the top 24 bits of one hash word (Mosaic has no uint32→f32 cast, and
24 bits are all an f32 mantissa holds): mean 0 and variance 1 up to
2^-48; tails truncate at ±6σ, far past anything the ±0.28-LSB thermal term
can push through the code rounding. The seed reaches the kernel as a
scalar-prefetch (SMEM) operand.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


# ---------------------------------------------------------------------------
# in-kernel counter-based PRNG (uint32 hash — works compiled AND interpreted)
# ---------------------------------------------------------------------------
def _mix32(h):
    """murmur3 finalizer: a bijective uint32 avalanche (all-ops VPU-native)."""
    h = h ^ (h >> jnp.uint32(16))
    h = h * jnp.uint32(0x85EBCA6B)
    h = h ^ (h >> jnp.uint32(13))
    h = h * jnp.uint32(0xC2B2AE35)
    h = h ^ (h >> jnp.uint32(16))
    return h


_GOLDEN32 = 0x9E3779B9  # 2^32/φ — the SplitMix increment


def _u32(x):
    """Reinterpret int32 bits as uint32 (a bitcast: Mosaic lowers it)."""
    return jax.lax.bitcast_convert_type(x, jnp.uint32)


def salt_seed(seed, salt):
    """Fold a decorrelation `salt` into an int32 kernel seed (XOR with the
    golden-ratio-scrambled salt; salt=0 is the identity).

    One seed names one stochastic converter instance, so any two kernel
    invocations that must draw independent noise need distinct effective
    seeds. Two salts exist, both built from this scheme: the STATIC
    inl_seed (per-layer/per-step decorrelation, applied by the kernel
    wrapper at trace time) and the TRACED `jax.lax.axis_index` salt the
    engine's mesh dispatch applies per shard, so every shard of a
    sharded MVM models its own macro's converter chain (the Fig. 18
    instance-to-instance spread, one instance per shard). Works on python
    ints and traced int32 scalars; integer multiply wraps mod 2^32, matching
    the in-kernel uint32 arithmetic bit-for-bit.
    """
    if isinstance(salt, int):
        salt &= 0xFFFFFFFF
        if salt >= 0x80000000:
            salt -= 0x100000000
    seed = jnp.asarray(seed, jnp.int32)
    return seed ^ (jnp.asarray(salt, jnp.int32) * jnp.int32(-1640531527))


def _seed_word(seed, inl_seed: int):
    """The kernel's int32 seed operand: the inl_seed-salted seed after the
    first absorption round of the counter hash (computed once per call,
    outside the grid)."""
    h = _mix32(_u32(salt_seed(seed, inl_seed)) ^ jnp.uint32(_GOLDEN32))
    return jax.lax.bitcast_convert_type(h, jnp.int32).reshape(1)


def _counter_base(word, rows, cols, group):
    """Per-element uint32 hash state from (seed word, global coords, group).

    Full 32-bit words are absorbed sequentially (sponge-style) instead of
    being packed into one index, so no shape is large enough to overflow the
    counter into systematic collisions. All operands are int32 arrays of
    the tile's shape.
    """
    h = _mix32(_u32(word) ^ _u32(rows))
    h = _mix32(h ^ _u32(cols))
    h = _mix32(h ^ (_u32(group) * jnp.uint32(0x01000193)))
    return h


def _normal12(base):
    """Standard-normal draw per element: Irwin–Hall sum of 12 uniforms.

    Draw j is SplitMix-style: mix(base + j·GOLDEN), whose top 24 bits k
    give the uniform (k + ½)·2^-24 — exact mean ½, variance (1 − 2^-48)/12.
    The twelve k sum exactly in int32 (< 2^28) — the distributional-
    agreement contract the engine tests check against the jax.random.normal
    reference path.
    """
    acc = jnp.zeros(base.shape, jnp.int32)
    for j in range(12):
        bits = _mix32(base + jnp.uint32((j + 1) * _GOLDEN32 & 0xFFFFFFFF))
        acc = acc + jax.lax.bitcast_convert_type(bits >> jnp.uint32(8),
                                                 jnp.int32)
    return acc.astype(jnp.float32) * jnp.float32(2.0 ** -24) \
        - jnp.float32(6.0 - 6.0 * 2.0 ** -24)


def _stochastic_codes(x, word, *, levels, sigma, inl_amp, inl_seed,
                      apply_inl):
    """NOISY/FULL TD-ADC transfer on one [bm, bn] tile already in LSB
    units, in VMEM → integer codes.

    Mirrors core.adc.adc_quantize order exactly: INL (FULL only, the same
    `inl_curve` instance for a given inl_seed) → thermal noise → clip/round.
    The draw is keyed by the output coordinate and the group, never by the
    weight container, so packed and unpacked kernels agree bit-for-bit.
    """
    if apply_inl:
        from repro.core.adc import inl_curve
        x = x + inl_curve(jnp.clip(x / float(levels), 0.0, 1.0), inl_amp,
                          inl_seed)
    bm, bn = x.shape
    rows = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 0) \
        + pl.program_id(0) * bm
    cols = jax.lax.broadcasted_iota(jnp.int32, (bm, bn), 1) \
        + pl.program_id(1) * bn
    group = jnp.full((bm, bn), pl.program_id(2), jnp.int32)
    base = _counter_base(jnp.full((bm, bn), word, jnp.int32), rows, cols,
                         group)
    x = x + jnp.float32(sigma) * _normal12(base)
    return jnp.clip(jnp.round(x), 0.0, float(levels - 1))


def _cim_mvm_kernel(*refs, inv_lsb: float, lsb: float, levels: int,
                    packed: bool, noise: dict | None):
    """One (bm × bn) output tile; sequential loop over macro groups.

    refs = ([seed_ref,] x_ref, w_ref, o_ref); seed_ref is the SMEM seed
    word of the stochastic variant. x_ref is [1, bm, 144] (dense) or
    [1, 2, bm, 72] (packed: even / odd reduction rows); w_ref is the
    group's [144, bn] f32 codes or [72, bn] u8 nibble pairs.
    """
    if noise is not None:
        seed_ref, x_ref, w_ref, o_ref = refs
    else:
        x_ref, w_ref, o_ref = refs
    g = pl.program_id(2)

    @pl.when(g == 0)
    def _init():
        o_ref[...] = jnp.zeros_like(o_ref)

    # Analog MAC: charge accumulation over one 144-row group (exact/linear).
    if packed:
        # weights travel HBM→VMEM at 4 bits each (the paper's 4-bit SRAM
        # storage density, 559 Kb/mm²) and unpack right before the MXU
        wp = w_ref[...].astype(jnp.int32)
        lo = (wp & 15).astype(jnp.float32)
        hi = ((wp >> 4) & 15).astype(jnp.float32)
        part = jnp.dot(x_ref[0, 0], lo, preferred_element_type=jnp.float32) \
            + jnp.dot(x_ref[0, 1], hi, preferred_element_type=jnp.float32)
    else:
        part = jnp.dot(x_ref[0], w_ref[...],
                       preferred_element_type=jnp.float32)
    # TD-ADC transfer: VTC gain + clip + round onto the 8.5-bit code grid.
    x = part * inv_lsb
    if noise is not None:
        code = _stochastic_codes(x, seed_ref[0], levels=levels, **noise)
    else:
        code = jnp.clip(jnp.round(x), 0.0, float(levels - 1))
    # Digital partial-sum accumulation, in integer codes.
    o_ref[...] += code

    @pl.when(g == pl.num_programs(2) - 1)
    def _reconstruct():
        o_ref[...] = o_ref[...] * lsb   # the ×LSB reconstruction


@functools.partial(
    jax.jit, static_argnames=("levels", "gain", "full_scale", "sigma",
                              "inl_amp", "inl_seed", "apply_inl", "bm", "bn",
                              "interpret"))
def cim_mvm_grouped(xg: jax.Array, w: jax.Array, seed: jax.Array | None = None,
                    *, levels: int, gain: float, full_scale: float,
                    sigma: float = 0.0, inl_amp: float = 0.0,
                    inl_seed: int = 0, apply_inl: bool = False,
                    bm: int = 128, bn: int = 128,
                    interpret: bool = False) -> jax.Array:
    """ŷ[M, N] = Σ_g ADC( x_g @ w[g·144:(g+1)·144] ), group-major operands.

    Dense: xg [G, M, n_rows] codes, w [G·n_rows, N] codes. Packed: xg
    [G, 2, M, n_rows/2] (even / odd reduction rows), w [G·n_rows/2, N]
    uint8 nibble pairs. ops.py builds both layouts and pads M/N to block
    multiples (zero codes are exact no-ops). `seed` (a TRACED int32
    scalar — no recompile when QAT varies it per step) selects the
    stochastic transfer; σ/INL settings are static, sourced from
    core.adc.stochastic_transfer_params.
    """
    packed = xg.ndim == 4
    groups, m = xg.shape[0], xg.shape[-2]
    rows_w = xg.shape[-1]          # weight rows per group: 144, or 72 bytes
    n = w.shape[1]
    assert w.shape[0] == groups * rows_w, (xg.shape, w.shape)
    bm = min(bm, m)
    bn = min(bn, n)
    assert m % bm == 0 and n % bn == 0, "caller pads M/N to block multiples"

    lsb = full_scale / (gain * (levels - 1))
    noise = None if seed is None else dict(
        sigma=sigma, inl_amp=inl_amp, inl_seed=inl_seed, apply_inl=apply_inl)
    kernel = functools.partial(_cim_mvm_kernel, inv_lsb=1.0 / lsb, lsb=lsb,
                               levels=levels, packed=packed, noise=noise)
    if packed:
        x_spec = pl.BlockSpec((1, 2, bm, rows_w),
                              lambda i, j, g, *_: (g, 0, i, 0))
    else:
        x_spec = pl.BlockSpec((1, bm, rows_w), lambda i, j, g, *_: (g, i, 0))
    w_spec = pl.BlockSpec((rows_w, bn), lambda i, j, g, *_: (g, j))
    o_spec = pl.BlockSpec((bm, bn), lambda i, j, g, *_: (i, j))
    grid = (m // bm, n // bn, groups)
    operands = (xg.astype(jnp.float32),
                w.astype(jnp.uint8 if packed else jnp.float32))
    if seed is None:
        grid_spec = pl.GridSpec(grid=grid, in_specs=[x_spec, w_spec],
                                out_specs=o_spec)
    else:
        grid_spec = pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=grid, in_specs=[x_spec, w_spec],
            out_specs=o_spec)
        operands = (_seed_word(seed, inl_seed),) + operands
    return pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((m, n), jnp.float32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
        name="cim_mvm",
    )(*operands)
