"""Bit-Parallel / Weight-Bit-Serial / Bit-Serial analog MVM flows.

This is the computational core of the paper (Eq. 1, 2, 7). All three schemes
share the same grouped integer MAC against offset-encoded unsigned codes; they
differ in *where the ADC quantizer sits*:

  BP  (Eq. 1):  ŷ = Σ_g Q_g( Σ_{i∈g} W̃_i X̃_i )                    1 ADC/group
  WBS:          ŷ = Σ_g Σ_p 2^p Q_g( Σ_{i∈g} W^p_i X̃_i )          B_W ADC/group
  BS  (Eq. 2):  ŷ = Σ_g Σ_p Σ_q 2^{p+q} Q_g( Σ_{i∈g} W^p_i X^q_i ) B_A·B_W ADC/group

with groups of N = 144 rows (partial-sum accumulation across macros when
K > N, paper §II-A) and Q the TD-ADC transfer with full scale matched to the
per-pass operand bit widths. The signed/affine correction (Eq. 7 generalized
to activation zero points) is applied digitally outside, see
`signed_correction`.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from .adc import adc_quantize
from .macro import MacroConfig, Scheme
from .quant import bit_planes


def pad_and_group(x: jax.Array, n_rows: int, axis: int = -1):
    """Zero-pad the reduction axis to a multiple of N and split into groups.

    Zero codes are exact no-ops in the analog array (an unselected row's
    C_MOM holds no DAC charge), so padding is free and bit-exact.
    """
    k = x.shape[axis]
    groups = max(1, -(-k // n_rows))
    pad = groups * n_rows - k
    if pad:
        widths = [(0, 0)] * x.ndim
        widths[axis % x.ndim] = (0, pad)
        x = jnp.pad(x, widths)
    new_shape = x.shape[:axis % x.ndim] + (groups, n_rows) + x.shape[axis % x.ndim + 1:]
    return x.reshape(new_shape), groups


def _grouped_mac(xg: jax.Array, wg: jax.Array) -> jax.Array:
    """Per-group integer MAC: xg [..., G, N] × wg [G, N, M] → [..., G, M].

    This is the analog charge accumulation on the MAC line; computed exactly
    (charge-domain accumulation is linear, R² = 0.9999 per Fig. 9 — the
    nonlinearity lives in the ADC model).
    """
    return jnp.einsum("...gn,gnm->...gm", xg, wg,
                      preferred_element_type=jnp.float32)


def _adc_sum(v: jax.Array, cfg: MacroConfig, key, ba: int, bw: int,
             inl_seed: int) -> jax.Array:
    """Quantize each group's analog value and digitally accumulate groups."""
    q = adc_quantize(v, cfg, key=key, act_bits_active=ba,
                     weight_bits_active=bw, inl_seed=inl_seed)
    return jnp.sum(q, axis=-2)  # digital partial-sum accumulation over G


def bp_mvm(x_codes: jax.Array, w_codes: jax.Array, cfg: MacroConfig, *,
           key: jax.Array | None = None, inl_seed: int = 0) -> jax.Array:
    """Bit-parallel (this work): one analog pass, one ADC per group."""
    xg, _ = pad_and_group(x_codes, cfg.n_rows)
    wg, _ = pad_and_group(w_codes, cfg.n_rows, axis=0)
    v = _grouped_mac(xg, wg)
    return _adc_sum(v, cfg, key, cfg.act_bits, cfg.weight_bits, inl_seed)


def wbs_mvm(x_codes: jax.Array, w_codes: jax.Array, cfg: MacroConfig, *,
            key: jax.Array | None = None, inl_seed: int = 0) -> jax.Array:
    """Weight-bit-serial baseline: B_W analog passes over weight bit planes."""
    xg, _ = pad_and_group(x_codes, cfg.n_rows)
    planes = bit_planes(w_codes, cfg.weight_bits)  # [B_W, K, M]
    out = 0.0
    for p in range(cfg.weight_bits):
        wg, _ = pad_and_group(planes[p], cfg.n_rows, axis=0)
        v = _grouped_mac(xg, wg)
        kp = None if key is None else jax.random.fold_in(key, p)
        out = out + (2 ** p) * _adc_sum(v, cfg, kp, cfg.act_bits, 1, inl_seed)
    return out


def bs_mvm(x_codes: jax.Array, w_codes: jax.Array, cfg: MacroConfig, *,
           key: jax.Array | None = None, inl_seed: int = 0) -> jax.Array:
    """Fully bit-serial baseline: B_A·B_W binary analog passes (Eq. 2)."""
    x_planes = bit_planes(x_codes, cfg.act_bits)    # [B_A, ..., K]
    w_planes = bit_planes(w_codes, cfg.weight_bits)  # [B_W, K, M]
    out = 0.0
    for p in range(cfg.weight_bits):
        wg, _ = pad_and_group(w_planes[p], cfg.n_rows, axis=0)
        for q in range(cfg.act_bits):
            xg, _ = pad_and_group(x_planes[q], cfg.n_rows)
            v = _grouped_mac(xg, wg)
            kpq = None if key is None else jax.random.fold_in(key, p * 16 + q)
            out = out + (2 ** (p + q)) * _adc_sum(v, cfg, kpq, 1, 1, inl_seed)
    return out


_SCHEME_FNS = {Scheme.BP: bp_mvm, Scheme.WBS: wbs_mvm, Scheme.BS: bs_mvm}


def cim_mvm_codes(x_codes: jax.Array, w_codes: jax.Array, cfg: MacroConfig, *,
                  key: jax.Array | None = None, inl_seed: int = 0) -> jax.Array:
    """Dispatch on the configured multi-bit scheme.

    x_codes [..., K] unsigned DAC codes; w_codes [K, M] unsigned stored codes.
    Returns ŷ ≈ Σ X̃ W̃ (float32, in integer MAC units).
    """
    return _SCHEME_FNS[cfg.scheme](x_codes, w_codes, cfg, key=key,
                                   inl_seed=inl_seed)


def exact_mvm_codes(x_codes: jax.Array, w_codes: jax.Array) -> jax.Array:
    """Infinite-resolution reference: y = Σ X̃ W̃ with no ADC (15-bit ADC limit
    in the paper's terms). Ground truth for SQNR (Eq. 3)."""
    return jnp.einsum("...k,km->...m", x_codes, w_codes,
                      preferred_element_type=jnp.float32)


def signed_correction(y_codes: jax.Array, x_codes: jax.Array,
                      w_codes: jax.Array | None = None, *, w_offset: int,
                      x_zero_point: jax.Array,
                      sum_w: jax.Array | None = None,
                      k: int | None = None) -> jax.Array:
    """Digital correction generalizing Eq. 7 to affine activations.

    With X = s_x (X̃ − z) and W = s_w (W̃ − o):
      Σ X W / (s_x s_w) = Σ X̃ W̃ − o Σ X̃ − z Σ W̃ + o z K
    The Σ X̃ term is the paper's shared adder tree; Σ W̃ is precomputable at
    weight-load time — pass it as `sum_w` (with the logical reduction
    length `k`) when the stored codes are not materialized, e.g. the
    engine's nibble-packed weight path. All exact integer arithmetic — no
    analog error.
    """
    if sum_w is None:
        sum_w = jnp.sum(w_codes, axis=-2)                   # [..., M]
    if k is None:
        k = x_codes.shape[-1]
    # f32 accumulation: codes arrive in the activation dtype, and a bf16
    # sum of K codes (≈ 10³) is not an integer-exact number
    sum_x = jnp.sum(x_codes, axis=-1, keepdims=True,
                    dtype=jnp.float32)                     # [..., 1]
    return (y_codes - w_offset * sum_x - x_zero_point * sum_w
            + w_offset * x_zero_point * k)
