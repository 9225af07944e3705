"""BP/WBS/BS computing-flow correctness (Eq. 1, 2, 7)."""
import dataclasses

import jax
import jax.numpy as jnp
import pytest
from hypothesis import given, settings, strategies as st

from repro.core import (CIMConfig, PROTOTYPE, Scheme, bp_mvm, bs_mvm,
                        cim_matmul, exact_mvm_codes, wbs_mvm)
from repro.core.schemes import pad_and_group, signed_correction


def _codes(key, shape, hi=16):
    return jax.random.randint(key, shape, 0, hi).astype(jnp.float32)


def _exact_cfg(scheme=Scheme.BP, n_rows=144):
    """ADC with LSB = 1 analog unit ⇒ bit-exact conversion of any level."""
    cfg = dataclasses.replace(PROTOTYPE, scheme=scheme, n_rows=n_rows)
    return dataclasses.replace(cfg, adc_levels=int(cfg.full_scale(
        1 if scheme is Scheme.BS else None,
        1 if scheme in (Scheme.BS, Scheme.WBS) else None)) + 1)


def test_bp_bit_exact_when_lsb_is_one():
    key = jax.random.PRNGKey(0)
    x = _codes(key, (4, 288))
    w = _codes(jax.random.fold_in(key, 1), (288, 8))
    cfg = dataclasses.replace(PROTOTYPE, adc_levels=32401)  # FS+1 levels
    assert jnp.array_equal(bp_mvm(x, w, cfg), exact_mvm_codes(x, w))


@pytest.mark.parametrize("fn,scheme", [(wbs_mvm, Scheme.WBS),
                                       (bs_mvm, Scheme.BS)])
def test_serial_schemes_bit_exact_at_full_resolution(fn, scheme):
    key = jax.random.PRNGKey(2)
    x = _codes(key, (3, 144))
    w = _codes(jax.random.fold_in(key, 3), (144, 5))
    cfg = _exact_cfg(scheme)
    assert jnp.array_equal(fn(x, w, cfg), exact_mvm_codes(x, w))


def test_signed_correction_sums_bf16_codes_exactly():
    """Activation codes arrive in the model's dtype (bf16 when serving);
    their sum over K = 2048 exceeds bf16's 8-bit mantissa, so the
    correction must accumulate it in f32 to stay integer-exact."""
    k = 2048
    x_codes = jax.random.randint(jax.random.PRNGKey(3), (4, k), 0, 16)
    w_codes = jax.random.randint(jax.random.PRNGKey(4), (k, 8), 0, 16)
    y = jnp.zeros((4, 8), jnp.float32)
    kw = dict(w_offset=8, x_zero_point=jnp.float32(3))
    exact = signed_correction(y, x_codes.astype(jnp.float32),
                              w_codes.astype(jnp.float32), **kw)
    served = signed_correction(y, x_codes.astype(jnp.bfloat16),
                               w_codes.astype(jnp.float32), **kw)
    assert served.dtype == jnp.float32
    assert bool(jnp.array_equal(served, exact))


def test_signed_correction_is_exact_integer_identity():
    """Eq. 7 (generalized): the offset/zero-point correction is exact."""
    key = jax.random.PRNGKey(4)
    x_codes = _codes(key, (6, 200))
    w_signed = jax.random.randint(jax.random.fold_in(key, 5), (200, 7),
                                  -8, 8).astype(jnp.float32)
    zp = jnp.asarray(5.0)
    w_codes = w_signed + 8.0
    y_unsigned = exact_mvm_codes(x_codes, w_codes)
    y = signed_correction(y_unsigned, x_codes, w_codes, w_offset=8,
                          x_zero_point=zp)
    y_ref = exact_mvm_codes(x_codes - zp, w_signed)
    assert jnp.array_equal(y, y_ref)


def test_pad_and_group_zero_pads_are_noops():
    x = jnp.ones((2, 150))
    xg, g = pad_and_group(x, 144)
    assert xg.shape == (2, 2, 144) and g == 2
    assert float(jnp.sum(xg)) == 300.0  # padding contributed zeros


def test_quantization_error_bounded_by_group_lsb():
    key = jax.random.PRNGKey(6)
    x = _codes(key, (8, 430))
    w = _codes(jax.random.fold_in(key, 7), (430, 3))
    cfg = PROTOTYPE  # 362 levels
    groups = -(-430 // 144)
    lsb = cfg.full_scale() / (cfg.gain * cfg.adc_levels)
    err = jnp.abs(bp_mvm(x, w, cfg) - exact_mvm_codes(x, w))
    assert float(err.max()) <= groups * lsb / 2 + 1e-3


def test_gain_reduces_quantization_error_for_small_signals():
    """Fig. 15/18: VTC gain shrinks the LSB when activations are small."""
    key = jax.random.PRNGKey(8)
    x = _codes(key, (16, 144), hi=4)    # small codes: top of range unused
    w = _codes(jax.random.fold_in(key, 9), (144, 4), hi=16)
    y_ref = exact_mvm_codes(x, w)
    errs = {}
    for gain in (1.0, 3.0):
        cfg = dataclasses.replace(PROTOTYPE, gain=gain)
        errs[gain] = float(jnp.mean(jnp.abs(bp_mvm(x, w, cfg) - y_ref)))
    assert errs[3.0] < errs[1.0]


def test_cim_matmul_relative_error_reasonable():
    """ReLU'd Gaussian activations underfill the DAC range at gain 1 — the
    exact situation the paper's VTC gain knob exists for (§V-A). At the
    deployed gain of 3 (Fig. 19) the 8.5-bit pipeline is accurate."""
    key = jax.random.PRNGKey(10)
    x = jax.nn.relu(jax.random.normal(key, (32, 288)))
    w = jax.random.normal(jax.random.fold_in(key, 11), (288, 16)) * 0.1
    yf = x @ w
    rel = {}
    for gain in (1.0, 3.0):
        cim = CIMConfig(enabled=True,
                        macro=dataclasses.replace(PROTOTYPE, gain=gain))
        y = cim_matmul(x, w, cim)
        rel[gain] = float(jnp.linalg.norm(y - yf) / jnp.linalg.norm(yf))
    assert rel[3.0] < rel[1.0]
    assert rel[3.0] < 0.25


@settings(max_examples=25, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 300), st.integers(1, 6))
def test_bp_exactness_property(seed, k, m):
    """Property: with LSB=1 the whole analog pipeline is lossless (the
    paper's '15-bit ADC covers every level' limit)."""
    key = jax.random.PRNGKey(seed)
    x = _codes(key, (2, k))
    w = _codes(jax.random.fold_in(key, 1), (k, m))
    cfg = dataclasses.replace(PROTOTYPE, adc_levels=int(PROTOTYPE.full_scale()) + 1)
    assert jnp.array_equal(bp_mvm(x, w, cfg), exact_mvm_codes(x, w))
