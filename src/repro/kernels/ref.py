"""Pure-jnp oracle for the cim_mvm Pallas kernel.

Mirrors the kernel's deterministic (SimLevel.IDEAL) BP transfer exactly:
grouped MAC → per-group ADC clip/round with VTC gain → digital accumulation.
The group sum runs over integer codes (exact in f32 at any order) and is
scaled by the LSB once, so the kernel must agree bit-for-bit.
Kept independent of core/schemes.py so kernel tests exercise a genuinely
separate code path (core uses STE rounding and richer noise models; the
numerics at IDEAL level must agree to float tolerance).
"""
from __future__ import annotations

import jax.numpy as jnp


def cim_mvm_ref(x_codes, w_codes, *, n_rows: int, levels: int, gain: float,
                full_scale: float):
    """x_codes [M, K], w_codes [K, N] (K a multiple of n_rows) → [M, N]."""
    m, k = x_codes.shape
    _, n = w_codes.shape
    groups = k // n_rows
    lsb = full_scale / (gain * (levels - 1))
    xg = x_codes.astype(jnp.float32).reshape(m, groups, n_rows)
    wg = w_codes.astype(jnp.float32).reshape(groups, n_rows, n)
    part = jnp.einsum("mgk,gkn->mgn", xg, wg,
                      preferred_element_type=jnp.float32)
    code = jnp.clip(jnp.round(part * (1.0 / lsb)), 0.0, float(levels - 1))
    return jnp.sum(code, axis=1) * lsb
