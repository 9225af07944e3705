"""Public jit'd wrapper around the cim_mvm Pallas kernel.

Handles: leading-dim flattening, zero-padding of K to the macro depth and of
M/N to block multiples (zero codes are unselected SRAM rows — bit-exact
no-ops), the group-major activation layout the kernel reads, the choice
between the compiled TPU kernel and interpret mode on CPU
(`kernels.interpret_mode`), and the bm/bn tile knobs.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.macro import MacroConfig, Scheme, SimLevel

from . import interpret_mode
from .cim_mvm import cim_mvm_grouped, salt_seed

__all__ = [
    "cim_mvm_pallas", "cim_mvm_pallas_packed", "cim_mvm_pallas_noisy",
    "cim_mvm_pallas_noisy_packed", "pack_codes", "unpack_codes",
    "packed_col_sums", "salt_seed",
]


def pack_codes(w_codes: jax.Array) -> jax.Array:
    """[..., K, N] 4-bit codes → [..., ceil(K/2), N] uint8 nibble pairs.

    Row 2i lands in the low nibble, row 2i+1 in the high nibble. Odd K is
    zero-padded first (a zero code is an unselected SRAM row — an exact
    no-op in the MVM and in the Eq. 7 correction sums). This is the
    wire/HBM format the packed kernel consumes — 4 bits per stored weight,
    as in the SRAM array. Leading dims (stacked layers, experts) pass
    through untouched.
    """
    k, n = w_codes.shape[-2:]
    if k % 2:
        widths = [(0, 0)] * (w_codes.ndim - 2) + [(0, 1), (0, 0)]
        w_codes = jnp.pad(w_codes, widths)
        k += 1
    wi = w_codes.astype(jnp.int32).reshape(*w_codes.shape[:-2], k // 2, 2, n)
    return (wi[..., 0, :] | (wi[..., 1, :] << 4)).astype(jnp.uint8)


def unpack_codes(w_packed: jax.Array, k: int | None = None) -> jax.Array:
    """Inverse of pack_codes: [..., K2, N] uint8 → [..., K, N] f32 codes.

    `k` trims the pack-padding row when the logical K was odd; defaults to
    the full 2·K2 rows.
    """
    wi = w_packed.astype(jnp.int32)
    lo = (wi & 15).astype(jnp.float32)
    hi = ((wi >> 4) & 15).astype(jnp.float32)
    k2, n = w_packed.shape[-2:]
    full = jnp.stack([lo, hi], axis=-2).reshape(*w_packed.shape[:-2],
                                                2 * k2, n)
    return full if k is None else full[..., :k, :]


def packed_col_sums(w_packed: jax.Array) -> jax.Array:
    """Σ_K W̃ per output column straight from the packed bytes — the Eq. 7
    ΣW̃ correction term without materializing unpacked codes (pack-padding
    rows hold zero codes, so they are exact no-ops in the sum)."""
    wi = w_packed.astype(jnp.int32)
    return jnp.sum((wi & 15) + ((wi >> 4) & 15), axis=-2).astype(jnp.float32)


def _resolve_tiles(x_codes, n: int, n_rows: int,
                   bm: int | None, bn: int | None) -> tuple[int, int]:
    """(bm, bn) for this MVM shape: explicit values win; None consults the
    kernels.autotune cache (env `REPRO_TUNE_CACHE`) under the "cim_mvm"
    kernel key — this is how core.engine.execute_mvm's Pallas backends,
    which call these entry points with no tile kwargs, pick up tuned tiles
    at dispatch. A miss keeps the (128, 128) defaults."""
    if bm is not None and bn is not None:
        return bm, bn
    from repro.kernels import autotune
    k = x_codes.shape[-1]
    m = 1
    for d in x_codes.shape[:-1]:
        m *= d
    tuned = autotune.lookup(
        "cim_mvm", autotune.mvm_family(m, -(-k // n_rows), n),
        backend="pallas")
    if autotune.cache_path():
        from repro.runtime.telemetry import KERNEL_COUNTERS
        KERNEL_COUNTERS.tune_lookup("cim_mvm", hit=tuned is not None)
    tuned = tuned or {}
    if bm is None:
        bm = int(tuned.get("bm", 128) or 128)
    if bn is None:
        bn = int(tuned.get("bn", 128) or 128)
    return max(1, bm), max(1, bn)


def _pad_to(x: jax.Array, multiple: int, axis: int) -> jax.Array:
    size = x.shape[axis]
    pad = (-size) % multiple
    if not pad:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


def _prep(x_codes, w, n_rows: int, bm: int, bn: int, packed: bool):
    """Operand prep shared by every entry: flatten leading dims, zero-pad K
    to the macro depth and M/N to block multiples (zero codes are
    unselected SRAM rows — exact no-ops), and lay x out group-major as the
    kernel reads it: [G, M, n_rows], or [G, 2, M, n_rows/2] split into
    even / odd reduction rows for nibble-packed w (zero bytes = two
    unselected rows). Returns (xg, w2, bm_eff, bn_eff, lead, m, n)."""
    lead = x_codes.shape[:-1]
    k = x_codes.shape[-1]
    x2 = x_codes.reshape(-1, k)
    m, n = x2.shape[0], w.shape[-1]
    if packed:
        assert k in (2 * w.shape[0], 2 * w.shape[0] - 1), \
            (x_codes.shape, w.shape)
        x2 = _pad_to(_pad_to(x2, 2, 1), n_rows, 1)
        w2 = _pad_to(w, n_rows // 2, 0)
    else:
        x2 = _pad_to(x2, n_rows, 1)
        w2 = _pad_to(w, n_rows, 0)
    x2 = _pad_to(x2, min(bm, max(m, 1)), 0)
    w2 = _pad_to(w2, min(bn, max(n, 1)), 1)
    mp, groups = x2.shape[0], x2.shape[1] // n_rows
    if packed:
        xg = x2.reshape(mp, groups, n_rows // 2, 2).transpose(1, 3, 0, 2)
    else:
        xg = x2.reshape(mp, groups, n_rows).transpose(1, 0, 2)
    bm_eff = bm if mp % bm == 0 else mp
    bn_eff = bn if w2.shape[1] % bn == 0 else w2.shape[1]
    return xg, w2, bm_eff, bn_eff, lead, m, n


def _run(x_codes, w, cfg: MacroConfig, *, packed: bool, noise_seed=None,
         inl_seed: int = 0, bm=None, bn=None, interpret=None) -> jax.Array:
    """Tiles, operand prep and the kernel call behind every entry point."""
    assert cfg.scheme == Scheme.BP, "fused kernel implements BP only"
    if packed:
        assert cfg.n_rows % 2 == 0, "nibble packing needs an even macro depth"
    interpret = interpret_mode(interpret)
    bm, bn = _resolve_tiles(x_codes, w.shape[-1], cfg.n_rows, bm, bn)
    xg, w2, bm_eff, bn_eff, lead, m, n = _prep(x_codes, w, cfg.n_rows, bm,
                                                bn, packed)
    kw = {}
    seed = None
    if noise_seed is not None:
        from repro.core.adc import stochastic_transfer_params
        kw = dict(stochastic_transfer_params(cfg), inl_seed=inl_seed)
        seed = jnp.asarray(noise_seed, jnp.int32)
    out = cim_mvm_grouped(
        xg, w2, seed, levels=cfg.effective_adc_levels(), gain=cfg.gain,
        full_scale=cfg.full_scale(), bm=bm_eff, bn=bn_eff,
        interpret=interpret, **kw)
    return out[:m, :n].reshape(*lead, n)


def cim_mvm_pallas_packed(x_codes: jax.Array, w_packed: jax.Array,
                          cfg: MacroConfig, *, bm: int | None = None,
                          bn: int | None = None,
                          interpret: bool | None = None) -> jax.Array:
    """ŷ ≈ Σ X̃ W̃ with 4-bit-packed weights. x [..., K], w_packed [K2, M]
    with K ≤ 2·K2 (K2 = ceil(K/2) nibble pairs). K, M and the leading dims
    are padded here; zero bytes are pairs of unselected SRAM rows."""
    return _run(x_codes, w_packed, cfg, packed=True, bm=bm, bn=bn,
                interpret=interpret)


def cim_mvm_pallas(x_codes: jax.Array, w_codes: jax.Array, cfg: MacroConfig,
                   *, bm: int | None = None, bn: int | None = None,
                   interpret: bool | None = None) -> jax.Array:
    """ŷ ≈ Σ X̃ W̃ through the fused BP kernel.

    x_codes [..., K] unsigned DAC codes, w_codes [K, M] stored codes.
    Only the BP scheme is implemented as a fused kernel — it is the paper's
    deployed scheme; WBS/BS baselines run on the jnp path.
    """
    return _run(x_codes, w_codes, cfg, packed=False, bm=bm, bn=bn,
                interpret=interpret)


def cim_mvm_pallas_noisy(x_codes: jax.Array, w_codes: jax.Array,
                         cfg: MacroConfig, *, noise_seed, inl_seed: int = 0,
                         bm: int | None = None, bn: int | None = None,
                         interpret: bool | None = None) -> jax.Array:
    """Stochastic (NOISY/FULL) fused BP MVM: per-conversion thermal noise
    (and, at FULL, the Fig. 15 INL instance for cfg's inl_seed) drawn inside
    the kernel in VMEM. `noise_seed` is a traced int32 scalar — vary it per
    QAT step without recompiling. σ/INL settings come from
    core.adc.stochastic_transfer_params, the same source adc_quantize uses,
    so the fused and jnp pipelines agree in distribution."""
    assert cfg.sim_level != SimLevel.IDEAL, \
        "IDEAL transfer runs the deterministic kernel (cim_mvm_pallas)"
    return _run(x_codes, w_codes, cfg, packed=False, noise_seed=noise_seed,
                inl_seed=inl_seed, bm=bm, bn=bn, interpret=interpret)


def cim_mvm_pallas_noisy_packed(x_codes: jax.Array, w_packed: jax.Array,
                                cfg: MacroConfig, *, noise_seed,
                                inl_seed: int = 0, bm: int | None = None,
                                bn: int | None = None,
                                interpret: bool | None = None) -> jax.Array:
    """Stochastic fused BP MVM over nibble-packed weights. Noise draws are a
    pure function of (seed, output coordinate, group) — independent of the
    weight container — so this is bit-identical to cim_mvm_pallas_noisy on
    the unpacked codes under the same seed."""
    assert cfg.sim_level != SimLevel.IDEAL
    return _run(x_codes, w_packed, cfg, packed=True, noise_seed=noise_seed,
                inl_seed=inl_seed, bm=bm, bn=bn, interpret=interpret)
