"""The serving path's kernels compile for a TPU v5e at internlm2-1.8b widths.

Interpret mode (every other kernel test) cannot reject a block shape the
chip's compiler refuses, so these tests compile the real-width kernels
against a described, unattached v5e topology: the four cim_mvm entries at
the model's (K, N) matmul shapes, paged flash attention for decode (C = 1)
and prefill (C = 16), and the fused decode write. Nothing runs; each test
compiles one kernel and checks that the compiled program holds a Mosaic
custom call. The topology is described inside a fixture (never at import),
so every test worker collects the same tests and only the worker given
this file loads the TPU compiler.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.core.macro import MacroConfig, SimLevel
from repro.kernels import ops
from repro.kernels import paged_attention as pa

# internlm2-1.8b matmuls (K, N): wq/wo, wk/wv, w_gate/w_up, w_down, head
MVM_SHAPES = [(2048, 2048), (2048, 1024), (2048, 8192), (8192, 2048),
              (2048, 92544)]
# paged serving step: 8 slots, 16/8 heads of 128, 16-token blocks, bf16
B, H, KH, DH, BS, MB = 8, 16, 8, 128, 16, 8


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    # a compile written to the persistent cache could not be read back
    # without the chip; keep these compiles out of it
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)


def _compile(fn, *args):
    text = jax.jit(fn).lower(*args).compile().as_text()
    assert "tpu_custom_call" in text
    return text


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("rows", [8, 24])
@pytest.mark.parametrize("k,n", MVM_SHAPES)
@pytest.mark.parametrize("entry", ["dense", "packed", "noisy",
                                   "noisy_packed"])
def test_cim_mvm_compiles(one_chip, entry, k, n, rows):
    ideal = MacroConfig()
    noisy = dataclasses.replace(ideal, sim_level=SimLevel.NOISY)
    x = _spec(one_chip, (rows, k), jnp.float32)
    packed = entry.endswith("packed")
    w = _spec(one_chip, (k // 2, n) if packed else (k, n),
              jnp.uint8 if packed else jnp.float32)
    seed = _spec(one_chip, (), jnp.int32)
    fns = {
        "dense": lambda x, w, s: ops.cim_mvm_pallas(x, w, ideal,
                                                    interpret=False),
        "packed": lambda x, w, s: ops.cim_mvm_pallas_packed(
            x, w, ideal, interpret=False),
        "noisy": lambda x, w, s: ops.cim_mvm_pallas_noisy(
            x, w, noisy, noise_seed=s, interpret=False),
        "noisy_packed": lambda x, w, s: ops.cim_mvm_pallas_noisy_packed(
            x, w, noisy, noise_seed=s, interpret=False),
    }
    _compile(fns[entry], x, w, seed)


def test_cim_mvm_full_sim_level_compiles(one_chip):
    """SimLevel.FULL adds the in-kernel INL curve (sines) to the noise."""
    full = dataclasses.replace(MacroConfig(), sim_level=SimLevel.FULL)
    _compile(lambda x, w, s: ops.cim_mvm_pallas_noisy_packed(
                 x, w, full, noise_seed=s, inl_seed=3, interpret=False),
             _spec(one_chip, (8, 2048), jnp.float32),
             _spec(one_chip, (1024, 2048), jnp.uint8),
             _spec(one_chip, (), jnp.int32))


@pytest.mark.parametrize("kblocks,row_tile", [(1, None), (4, None), (2, 8)])
@pytest.mark.parametrize("c", [1, 16])
def test_paged_flash_attention_compiles(one_chip, c, kblocks, row_tile):
    pool = (B * MB + 1, KH, BS, DH)
    _compile(lambda q, kp, vp, t, ln, kvl: pa.paged_flash_attention(
                 q, kp, vp, t, ln, kvl, interpret=False, kblocks=kblocks,
                 row_tile=row_tile),
             _spec(one_chip, (B, c, H, DH), jnp.bfloat16),
             _spec(one_chip, pool, jnp.bfloat16),
             _spec(one_chip, pool, jnp.bfloat16),
             _spec(one_chip, (B, MB), jnp.int32),
             _spec(one_chip, (B,), jnp.int32),
             _spec(one_chip, (B,), jnp.int32))


def test_fused_paged_write_compiles(one_chip):
    pool = (B * MB + 1, KH, BS, DH)
    _compile(lambda kp, vp, nk, nv, fi: pa.fused_paged_write(
                 kp, vp, nk, nv, fi, interpret=False),
             _spec(one_chip, pool, jnp.bfloat16),
             _spec(one_chip, pool, jnp.bfloat16),
             _spec(one_chip, (B, 1, KH, DH), jnp.bfloat16),
             _spec(one_chip, (B, 1, KH, DH), jnp.bfloat16),
             _spec(one_chip, (B, 1), jnp.int32))
