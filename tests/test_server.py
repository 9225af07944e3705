"""Slot-based serving loop: drains, respects slots, matches single-request
greedy decoding."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKES
from repro.models import registry
from repro.runtime.server import Request, Server, ServingConfig


@pytest.fixture(scope="module")
def setup():
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)
    return cfg, params


def _greedy_reference(cfg, params, prompt, n_new):
    mod = registry.get_module(cfg)
    logits, cache = mod.prefill(
        params, {"tokens": jnp.asarray([prompt], jnp.int32)}, cfg, max_len=64)
    out = [int(jnp.argmax(logits[0]))]
    for _ in range(n_new - 1):
        logits, cache = mod.decode_step(
            params, jnp.asarray([[out[-1]]], jnp.int32), cache, cfg)
        out.append(int(jnp.argmax(logits[0])))
    return out


def test_single_request_matches_reference(setup):
    cfg, params = setup
    server = Server(params, cfg, ServingConfig(n_slots=1, max_len=64))
    req = Request(prompt=[5, 9, 2, 7], max_new_tokens=6)
    server.submit(req)
    server.run_until_drained()
    assert req.done
    ref = _greedy_reference(cfg, params, req.prompt, 6)
    assert req.output == ref


def test_multi_request_batching_drains(setup):
    cfg, params = setup
    server = Server(params, cfg, ServingConfig(n_slots=2, max_len=64))
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=int(rng.randint(3, 9))).tolist(),
                    max_new_tokens=4) for _ in range(5)]
    for r in reqs:
        server.submit(r)
    server.run_until_drained()
    assert all(r.done for r in reqs)
    assert all(len(r.output) == 4 for r in reqs)


def test_eos_retires_slot(setup):
    cfg, params = setup
    server = Server(params, cfg, ServingConfig(n_slots=1, max_len=64))
    ref = _greedy_reference(cfg, params, [1, 2, 3], 8)
    eos = ref[2]  # force an early stop at the 3rd generated token
    req = Request(prompt=[1, 2, 3], max_new_tokens=8, eos_id=eos)
    server.submit(req)
    server.run_until_drained()
    assert req.done and len(req.output) == 3


def test_prequant_packed_serving_matches_unpacked():
    """End-to-end packed-int4 serving: the server's nibble-packed stored-code
    params produce EXACTLY the int8-container path's tokens (packing is a
    lossless re-layout), and the decode params really are 4-bit-packed."""
    from repro.core.cim_matmul import CIMConfig
    from repro.models.quantize import quantize_params

    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32",
                                           cim=CIMConfig(enabled=True))
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=64)
    outs = {}
    for packed in (True, False):
        server = Server(params, cfg, ServingConfig(
            n_slots=1, max_len=64, prequant=True, packed=packed))
        if packed:
            q = [v for k, v in jax.tree_util.tree_flatten_with_path(
                     server.params)[0]
                 if str(k[-1]).find("_q") >= 0]
            assert q and all(a.dtype == jnp.uint8 for a in q)
        req = Request(prompt=[5, 9, 2, 7], max_new_tokens=4)
        server.submit(req)
        server.run_until_drained()
        assert req.done
        outs[packed] = req.output
    assert outs[True] == outs[False]


def test_compile_cache_dir(monkeypatch, tmp_path):
    """serve.py's compile cache: JAX_COMPILATION_CACHE_DIR when it is set
    (left to JAX, no other dir set in code), else the fixed, gitignored
    `<repo>/.jax_cache`."""
    import os

    from repro.launch import compile_cache
    was = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == was
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    try:
        path = compile_cache.enable_compile_cache()
        assert path == compile_cache.REPO_CACHE_DIR
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
    root, name = os.path.split(path)
    assert name == ".jax_cache"
    with open(os.path.join(root, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
