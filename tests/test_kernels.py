"""Pallas cim_mvm kernel vs pure-jnp oracle: shape/dtype sweeps + properties.

interpret=True executes the kernel body on CPU. The kernel and the oracle
both accumulate integer ADC codes (exact in f32 in any order) and scale by
the LSB once, so they agree bit-for-bit; comparisons against the core jnp
pipeline keep a float tolerance (it sums LSB-scaled partials).

The whole module calls the Pallas kernels directly, so it is skipped under
REPRO_FORCE_JNP=1 — that CI leg models an environment WITHOUT interpret-mode
Pallas support, where only the jnp engine backends (and the auto-selection
escape hatch routing to them) must stay green.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from hypothesis import given, settings, strategies as st

pytestmark = pytest.mark.skipif(
    os.environ.get("REPRO_FORCE_JNP", "").strip().lower()
    in ("1", "true", "yes"),
    reason="direct Pallas kernel tests; REPRO_FORCE_JNP leg is jnp-only")

from repro.core.macro import MacroConfig, SimLevel
from repro.core.schemes import bp_mvm
from repro.kernels.ops import cim_mvm_pallas
from repro.kernels.ref import cim_mvm_ref


def _codes(key, shape, dtype=jnp.float32):
    return jax.random.randint(key, shape, 0, 16).astype(dtype)


@pytest.mark.parametrize("m,k,n", [
    (1, 1, 1), (4, 144, 8), (16, 288, 32), (128, 144, 128),
    (130, 1000, 257), (7, 2048, 9), (256, 4320, 64),
])
def test_kernel_matches_ref_shapes(m, k, n):
    key = jax.random.PRNGKey(m * 1000 + k + n)
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    cfg = MacroConfig()
    y_k = cim_mvm_pallas(x, w, cfg)
    kp = -(-k // cfg.n_rows) * cfg.n_rows
    xp = jnp.pad(x, ((0, 0), (0, kp - k)))
    wp = jnp.pad(w, ((0, 0), (0, 0))) if kp == k else \
        jnp.pad(w, ((0, kp - k), (0, 0)))
    y_r = cim_mvm_ref(xp, wp, n_rows=cfg.n_rows, levels=cfg.adc_levels,
                      gain=cfg.gain, full_scale=cfg.full_scale())
    # integer code accumulation on both sides: bit-identical
    np.testing.assert_array_equal(np.asarray(y_k), np.asarray(y_r))


@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16, jnp.int8])
def test_kernel_input_dtypes(dtype):
    key = jax.random.PRNGKey(7)
    x = _codes(key, (8, 288), dtype)
    w = _codes(jax.random.fold_in(key, 8), (288, 16), dtype)
    cfg = MacroConfig()
    y = cim_mvm_pallas(x, w, cfg)
    y_core = bp_mvm(x.astype(jnp.float32), w.astype(jnp.float32), cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y_core),
                               rtol=1e-6, atol=1e-1)


@pytest.mark.parametrize("gain,levels", [(1.0, 362), (2.0, 362), (4.0, 256),
                                         (1.0, 1024)])
def test_kernel_gain_and_levels(gain, levels):
    key = jax.random.PRNGKey(9)
    x = _codes(key, (16, 144))
    w = _codes(jax.random.fold_in(key, 10), (144, 8))
    cfg = MacroConfig(gain=gain, adc_levels=levels)
    y_k = cim_mvm_pallas(x, w, cfg)
    y_c = bp_mvm(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c),
                               rtol=1e-6, atol=1e-1)


@pytest.mark.parametrize("bm,bn", [(8, 8), (32, 128), (128, 32)])
def test_kernel_block_shape_invariance(bm, bn):
    """Output must not depend on the VMEM tile choice."""
    key = jax.random.PRNGKey(11)
    x = _codes(key, (64, 432))
    w = _codes(jax.random.fold_in(key, 12), (432, 64))
    cfg = MacroConfig()
    base = cim_mvm_pallas(x, w, cfg)
    tiled = cim_mvm_pallas(x, w, cfg, bm=bm, bn=bn)
    np.testing.assert_allclose(np.asarray(base), np.asarray(tiled),
                               rtol=1e-6, atol=1e-2)


def test_kernel_batched_leading_dims():
    key = jax.random.PRNGKey(13)
    x = _codes(key, (2, 3, 5, 288))
    w = _codes(jax.random.fold_in(key, 14), (288, 16))
    cfg = MacroConfig()
    y = cim_mvm_pallas(x, w, cfg)
    assert y.shape == (2, 3, 5, 16)
    y2 = bp_mvm(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y), np.asarray(y2),
                               rtol=1e-6, atol=1e-1)


@settings(max_examples=20, deadline=None)
@given(st.integers(0, 10_000), st.integers(1, 96), st.integers(1, 500),
       st.integers(1, 40))
def test_kernel_property_random_shapes(seed, m, k, n):
    key = jax.random.PRNGKey(seed)
    x = _codes(key, (m, k))
    w = _codes(jax.random.fold_in(key, 1), (k, n))
    cfg = MacroConfig()
    y_k = cim_mvm_pallas(x, w, cfg)
    y_c = bp_mvm(x, w, cfg)
    np.testing.assert_allclose(np.asarray(y_k), np.asarray(y_c),
                               rtol=1e-6, atol=1e-1)


def test_kernel_exact_when_lsb_one():
    """Same losslessness property as the core pipeline."""
    key = jax.random.PRNGKey(15)
    x = _codes(key, (32, 288))
    w = _codes(jax.random.fold_in(key, 16), (288, 24))
    cfg = MacroConfig(adc_levels=32401)
    y = cim_mvm_pallas(x, w, cfg)
    assert jnp.array_equal(y, jnp.einsum("mk,kn->mn", x, w))


def test_packed_kernel_matches_unpacked():
    """4-bit-packed weights (2 codes/byte) must agree with the plain kernel
    — same math, quarter the weight HBM bytes."""
    from repro.kernels.ops import cim_mvm_pallas_packed, pack_codes
    key = jax.random.PRNGKey(21)
    cfg = MacroConfig()
    x = _codes(key, (32, 432))          # 3 macro groups, even K
    w = _codes(jax.random.fold_in(key, 22), (432, 24))
    y_plain = cim_mvm_pallas(x, w, cfg)
    y_packed = cim_mvm_pallas_packed(x, pack_codes(w), cfg)
    np.testing.assert_allclose(np.asarray(y_packed), np.asarray(y_plain),
                               rtol=1e-6, atol=1e-2)


def test_pack_codes_roundtrip():
    from repro.kernels.ops import pack_codes
    w = _codes(jax.random.PRNGKey(23), (10, 7))
    p = np.asarray(pack_codes(w))
    lo, hi = p & 15, (p >> 4) & 15
    recon = np.stack([lo, hi], 1).reshape(10, 7)
    np.testing.assert_array_equal(recon, np.asarray(w))


def test_interpret_mode_only_on_cpu(monkeypatch):
    """The kernels interpret on the CPU backend and compile on the TPU; any
    other backend raises instead of silently interpreting — at every
    kernel entry point, before anything is traced."""
    from repro.kernels import interpret_mode
    from repro.kernels.ops import (cim_mvm_pallas_noisy,
                                   cim_mvm_pallas_noisy_packed,
                                   cim_mvm_pallas_packed, pack_codes)
    from repro.kernels.paged_attention import (fused_paged_write,
                                               paged_flash_attention)
    cfg = MacroConfig()
    noisy = dataclasses.replace(cfg, sim_level=SimLevel.NOISY)
    x = _codes(jax.random.PRNGKey(31), (4, 144))
    w = _codes(jax.random.PRNGKey(32), (144, 8))
    pool = jnp.zeros((3, 1, 4, 8))
    new = jnp.zeros((1, 1, 1, 8))
    tables = jnp.ones((1, 2), jnp.int32)
    lens = jnp.zeros((1,), jnp.int32)
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    assert interpret_mode() is False
    monkeypatch.setattr(jax, "default_backend", lambda: "cpu")
    assert interpret_mode() is True
    monkeypatch.setattr(jax, "default_backend", lambda: "gpu")
    assert interpret_mode(True) is True      # an explicit choice wins
    calls = [
        lambda: interpret_mode(),
        lambda: cim_mvm_pallas(x, w, cfg),
        lambda: cim_mvm_pallas_packed(x, pack_codes(w), cfg),
        lambda: cim_mvm_pallas_noisy(x, w, noisy, noise_seed=1),
        lambda: cim_mvm_pallas_noisy_packed(x, pack_codes(w), noisy,
                                            noise_seed=1),
        lambda: paged_flash_attention(jnp.zeros((1, 1, 1, 8)), pool, pool,
                                      tables, lens, lens + 1),
        lambda: fused_paged_write(pool, pool, new, new,
                                  jnp.zeros((1, 1), jnp.int32)),
    ]
    for call in calls:
        with pytest.raises(RuntimeError, match="'gpu'"):
            call()
