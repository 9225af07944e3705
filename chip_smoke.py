#!/usr/bin/env python3
"""On-chip smoke run: internlm2-1.8b at published width, served on a TPU.

Drives the serving main path once, through the entry points a user calls
(`launch.serve`'s flags → ServingConfig → Server), with random weights
from seed 0 (24 layers, d 2048, 16/8 heads, d_ff 8192, vocab 92544):

  (a) the fused CIM kernels at the model's matmul shapes against the
      `kernels/ref.py` oracle: IDEAL dense and nibble-packed bit-identical;
      the stochastic kernels (packed ≡ unpacked) within the mean / σ
      bounds of the jnp einsum converter chain;
  (b) kernel attention against exact, on the op at the served shapes and
      on a prefill step of the float (--cim off) build; then
      `serve.py --full --paged --cim bp-prequant --attn kernel`: the
      compiled paged step holds Mosaic kernels (tpu_custom_call), and 8
      seeded requests × 16 new tokens all retire, with in-vocabulary
      tokens and finite logits at every step;
  (c) a few decode steps of `--cim bp-noisy` on the same weights.

    python chip_smoke.py               # one chip: phases (a), (b), (c)
    python chip_smoke.py --four-chip   # only: the (b) requests served
                                       # unsharded on device 0 and sharded
                                       # over a 4-chip mesh (--mesh host);
                                       # greedy streams must be equal, so
                                       # XLA runs without excess precision

Each phase prints one line of readings: compile seconds, tokens, peak
device memory, and a tok/s that is a smoke reading, not a benchmark. The
last line is `{"ok": true, "device": {...}}`. A failed check raises, so the
script then exits non-zero and prints no such line. It refuses to run
without a TPU, and under REPRO_FORCE_JNP (which swaps every kernel for jnp).
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

# internlm2-1.8b matmuls (K, N): wq/wo, wk/wv, w_gate/w_up, w_down, head
MVM_SHAPES = ((2048, 2048), (2048, 1024), (2048, 8192), (8192, 2048),
              (2048, 92544))
MVM_ROWS = (8, 24)
SERVE_FLAGS = ("--full", "--paged", "--attn", "kernel", "--slots", "8",
               "--max-len", "128")
PREQUANT_FLAGS = SERVE_FLAGS + ("--cim", "bp-prequant", "--requests", "8",
                                "--max-new", "16")
NOISY_FLAGS = SERVE_FLAGS + ("--cim", "bp-noisy", "--requests", "4",
                             "--max-new", "4")
# Kernel vs exact paged attention. They differ in the order of f32 softmax
# sums, and exact also rounds its softmax weights to bf16 before the PV
# product: a few bf16 roundings (2^-9 relative each; rel L2 ≈ 3e-3 in
# interpret mode) against an O(1) error from a wrong mask, block or head.
ATTN_REL_L2 = 1e-2
# The same difference in every layer's attention output of the float
# (--cim off) build, carried through 24 residual layers to the logits:
# rel L2 ≈ 2e-2 in interpret mode at 24 layers (d 128 and 256); a wrong
# kernel decorrelates the logits (rel L2 ≈ 1.4).
LOGIT_REL_L2 = 0.1


def _memory(device) -> str:
    """Device memory now and the process's peak so far."""
    stats = device.memory_stats() or {}
    return (f"{stats.get('bytes_in_use', 0) / 2 ** 30:.2f} GiB in use, "
            f"peak so far {stats.get('peak_bytes_in_use', 0) / 2 ** 30:.2f}"
            " GiB")


def phase_kernels(shapes=MVM_SHAPES, rows=MVM_ROWS, seed=0) -> None:
    """(a) cim_mvm, all four entries, against kernels/ref.py."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.core.engine import get_backend
    from repro.core.macro import MacroConfig, SimLevel
    from repro.kernels import ops, ref

    ideal = MacroConfig()
    noisy = dataclasses.replace(ideal, sim_level=SimLevel.NOISY)
    levels = ideal.effective_adc_levels()
    lsb = ideal.full_scale() / (ideal.gain * (levels - 1))
    key = jax.random.PRNGKey(seed)
    t0 = time.perf_counter()
    ratios, biases = [], []
    for m in rows:
        for k, n in shapes:
            key, kx, kw, kn = jax.random.split(key, 4)
            x = jax.random.randint(kx, (m, k), 0, 16).astype(jnp.float32)
            w = jax.random.randint(kw, (k, n), 0, 16).astype(jnp.float32)
            w_packed = ops.pack_codes(w)
            kp = -(-k // ideal.n_rows) * ideal.n_rows
            y_ref = ref.cim_mvm_ref(
                jnp.pad(x, ((0, 0), (0, kp - k))),
                jnp.pad(w, ((0, kp - k), (0, 0))), n_rows=ideal.n_rows,
                levels=levels, gain=ideal.gain,
                full_scale=ideal.full_scale())
            for name, y in (("dense", ops.cim_mvm_pallas(x, w, ideal)),
                            ("packed", ops.cim_mvm_pallas_packed(
                                x, w_packed, ideal))):
                if not bool(jnp.array_equal(y, y_ref)):
                    raise AssertionError(
                        f"IDEAL {name} cim_mvm {m}x{k}x{n} is not "
                        "bit-identical to kernels/ref.py: max |Δ| "
                        f"{float(jnp.max(jnp.abs(y - y_ref)))}")
            noise_seed = int(jax.random.randint(kn, (), 0, 2 ** 30))
            y_n = ops.cim_mvm_pallas_noisy(x, w, noisy,
                                           noise_seed=noise_seed)
            y_np = ops.cim_mvm_pallas_noisy_packed(x, w_packed, noisy,
                                                   noise_seed=noise_seed)
            if not bool(jnp.array_equal(y_n, y_np)):
                raise AssertionError(f"noisy packed ≠ unpacked {m}x{k}x{n}")
            y_e = get_backend("einsum").fn(x, w, noisy, key=kn)
            e_k = np.asarray(y_n - y_ref, np.float64).ravel() / lsb
            e_e = np.asarray(y_e - y_ref, np.float64).ravel() / lsb
            ratio = e_k.std() / e_e.std()
            bias = abs(e_k.mean() - e_e.mean()) / (e_e.std()
                                                   / np.sqrt(e_e.size))
            # the contract tests/test_engine.py pins at small sizes
            if not (0.85 < ratio < 1.18 and bias < 6.0):
                raise AssertionError(
                    f"noisy cim_mvm {m}x{k}x{n}: σ ratio {ratio:.4f}, "
                    f"mean offset {bias:.2f} standard errors vs einsum")
            ratios.append(ratio)
            biases.append(bias)
    print(f"[a] cim_mvm: {len(ratios)} shapes, IDEAL dense+packed "
          f"bit-identical to ref; noisy σ ratio {min(ratios):.4f}.."
          f"{max(ratios):.4f}, max mean offset {max(biases):.2f} s.e.; "
          f"{time.perf_counter() - t0:.1f}s incl. compiles; "
          f"{_memory(jax.devices()[0])}", flush=True)


def _prefill_inputs(server, reqs):
    """One paged step that prefills every prompt at once (each fits one
    chunk): tokens [B, C], a fresh pool, distinct blocks per slot."""
    import jax.numpy as jnp
    import numpy as np
    b, c = server.n_slots, server.prefill_chunk
    assert len(reqs) <= b and all(len(r.prompt) <= c for r in reqs)
    tokens = np.zeros((b, c), np.int32)
    valid = np.zeros(b, np.int32)
    for s, r in enumerate(reqs):
        tokens[s, :len(r.prompt)] = r.prompt
        valid[s] = len(r.prompt)
    mb = server.max_len // server.block_size
    tables = 1 + np.arange(b * mb, dtype=np.int32).reshape(b, mb)
    cache = server.mod.init_paged_cache(
        server.cfg, server.alloc.stats.num_blocks + 1, server.block_size)
    return (jnp.asarray(tokens), cache, jnp.asarray(tables),
            jnp.zeros(b, jnp.int32), jnp.asarray(valid))


def _guard_finite(server):
    """Wrap the server's step functions so every step's logits are checked
    for NaN/inf on the device (one bool crosses to the host per step)."""
    import jax.numpy as jnp
    for name in ("_pstep", "_pstep_all"):
        step = getattr(server, name)

        def checked(*a, _step=step):
            logits, cache = _step(*a)
            if not bool(jnp.all(jnp.isfinite(logits))):
                raise AssertionError("non-finite logits in a serving step")
            return logits, cache
        setattr(server, name, checked)


def _serve(server, reqs) -> tuple[float, int]:
    """Submit every request, drain, check each retired in-vocabulary."""
    t0 = time.perf_counter()
    for r in reqs:
        server.submit(r)
    server.run_until_drained()
    dt = time.perf_counter() - t0
    vocab = server.cfg.vocab
    for r in reqs:
        if not (r.done and len(r.output) == r.max_new_tokens
                and all(0 <= t < vocab for t in r.output)):
            raise AssertionError(f"request {r.rid} did not retire cleanly: "
                                 f"done={r.done} output={r.output}")
    return dt, sum(len(r.output) for r in reqs)


def _rel_l2(got, want) -> float:
    import numpy as np
    got = np.asarray(got, np.float64)
    want = np.asarray(want, np.float64)
    if not np.all(np.isfinite(got)):
        return float("inf")
    return float(np.linalg.norm(got - want) / np.linalg.norm(want))


def _attention_check(server, seed=0) -> list[float]:
    """Kernel vs exact paged attention at the served shapes (decode C = 1
    and a prefill chunk), on random pools in the model's dtype at mixed
    depths. Returns the rel L2 errors."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.kernels.paged_attention import paged_attention
    cfg = server.cfg
    b, bs = server.n_slots, server.block_size
    mb = server.max_len // bs
    pool = (b * mb + 1, cfg.n_kv_heads, bs, cfg.head_dim)
    dt = server.cache["layers"]["k"].dtype
    key = jax.random.PRNGKey(seed)
    tables = jnp.asarray(1 + np.arange(b * mb).reshape(b, mb), jnp.int32)
    rels = []
    for c in (1, server.prefill_chunk):
        kq, kk, kv, kl = jax.random.split(jax.random.fold_in(key, c), 4)
        q = jax.random.normal(kq, (b, c, cfg.n_heads, cfg.head_dim), dt)
        k_pool = jax.random.normal(kk, pool, dt)
        v_pool = jax.random.normal(kv, pool, dt)
        lens = jax.random.randint(kl, (b,), 0, mb * bs - c + 1)
        positions = lens[:, None] + jnp.arange(c)[None, :]
        out = {name: paged_attention(q, k_pool, v_pool, tables,
                                     positions=positions, kv_len=lens + c,
                                     backend=name)
               for name in ("kernel", "exact")}
        rels.append(_rel_l2(out["kernel"], out["exact"]))
    return rels


def phase_serve(flags, params) -> None:
    """(b) bp-prequant paged serving with the Pallas attention kernel."""
    import copy

    import jax
    import numpy as np
    from repro.launch import serve
    args = serve.build_parser().parse_args(list(flags))

    # kernel vs exact attention, where a tolerance can be tight: the op at
    # the served shapes, and the float build of the same weights. Under
    # CIM the comparison says nothing: at random weights the 4-bit
    # dynamic activation grid makes the network chaotic (a 3 % embedding
    # perturbation decorrelates its logits), so one bf16 rounding apart
    # is as far apart as any two inputs.
    float_args = copy.copy(args)
    float_args.cim = "off"
    server = serve.build_server(float_args, params=params)
    reqs = serve.synthetic_requests(args, server.cfg.vocab)
    attn_rels = _attention_check(server)
    inputs = _prefill_inputs(server, reqs)
    logits = {}
    for name in ("kernel", "exact"):
        cfg = server.cfg.replace(attn_backend=name)
        logits[name] = jax.jit(
            lambda p, *a, cfg=cfg: server.mod.paged_step(p, *a, cfg))(
                server.params, *inputs)[0]
    rel = _rel_l2(logits["kernel"], logits["exact"])
    agree = int(np.sum(np.asarray(logits["kernel"]).argmax(-1)
                       == np.asarray(logits["exact"]).argmax(-1)))
    print(f"[b] kernel vs exact attention: op rel L2 "
          f"{', '.join(f'{r:.2e}' for r in attn_rels)} (C = 1, "
          f"{server.prefill_chunk}; bound {ATTN_REL_L2}); float-build "
          f"prefill logits rel L2 {rel:.3e} (bound {LOGIT_REL_L2}), argmax "
          f"{agree}/{len(reqs)}; {_memory(jax.devices()[0])}", flush=True)
    if max(attn_rels) > ATTN_REL_L2 or rel > LOGIT_REL_L2:
        raise AssertionError("kernel attention disagrees with exact")
    del server, logits

    t0 = time.perf_counter()
    server = serve.build_server(args, params=params)
    t_build = time.perf_counter() - t0
    inputs = _prefill_inputs(server, reqs)
    t0 = time.perf_counter()
    compiled = server._pstep.lower(server.params, *inputs).compile()
    t_compile = time.perf_counter() - t0
    # Pallas kernels compiled for the chip, not interpreted
    n_kernels = compiled.as_text().count("tpu_custom_call")
    if not n_kernels:
        raise AssertionError("compiled paged step holds no tpu_custom_call")
    if not np.all(np.isfinite(np.asarray(
            compiled(server.params, *inputs)[0]))):
        raise AssertionError("non-finite logits in the prefill step")
    print(f"[b] bp-prequant paged step: {n_kernels} tpu_custom_call, "
          f"compiled in {t_compile:.1f}s; server built (offline packing) "
          f"in {t_build:.1f}s; {_memory(jax.devices()[0])}", flush=True)

    _guard_finite(server)
    dt, tokens = _serve(server, reqs)
    print(f"[b] served {len(reqs)} requests, {tokens} tokens in "
          f"{server.steps_run} steps, {dt:.1f}s incl. compiles "
          f"(smoke reading {tokens / dt:.1f} tok/s, not a benchmark); "
          f"{_memory(jax.devices()[0])}", flush=True)


def phase_noisy(flags, params) -> None:
    """(c) a few decode steps through the stochastic fused kernels."""
    import jax
    from repro.launch import serve
    args = serve.build_parser().parse_args(list(flags))
    server = serve.build_server(args, params=params)
    _guard_finite(server)
    reqs = serve.synthetic_requests(args, server.cfg.vocab)
    dt, tokens = _serve(server, reqs)
    print(f"[c] bp-noisy: {len(reqs)} requests, {tokens} tokens in "
          f"{server.steps_run} steps, {dt:.1f}s incl. compiles; "
          f"{_memory(jax.devices()[0])}", flush=True)


def phase_four_chip(flags, params) -> None:
    """The (b) requests unsharded on device 0, then sharded over the
    four-chip serving mesh (`serve.py --mesh host`), in one process."""
    import jax
    from repro.launch import serve
    from repro.parallel import sharding
    devices = jax.devices()
    streams = {}
    for mesh_flag in ("none", "host"):
        args = serve.build_parser().parse_args(
            list(flags) + ["--mesh", mesh_flag])
        mesh = serve.install_mesh(args)
        try:
            with (mesh if mesh is not None else jax.default_device(
                    devices[0])):
                server = serve.build_server(args, params=params)
                _guard_finite(server)
                reqs = serve.synthetic_requests(args, server.cfg.vocab)
                dt, tokens = _serve(server, reqs)
        finally:
            sharding.set_mesh(None)
        streams[mesh_flag] = [r.output for r in reqs]
        leaves = jax.tree_util.tree_leaves(server.params)
        spread = {d for a in leaves for d in a.sharding.device_set}
        split = sum(not a.sharding.is_fully_replicated for a in leaves)
        in_use = [(d.memory_stats() or {}).get("bytes_in_use", 0) / 2 ** 30
                  for d in devices]
        print(f"[4] mesh={mesh_flag}: {tokens} tokens in {server.steps_run} "
              f"steps, {dt:.1f}s incl. compiles; params on "
              f"{len(spread)} device(s), {split}/{len(leaves)} leaves "
              f"split; GiB in use per device "
              f"{[round(g, 2) for g in in_use]}", flush=True)
        if mesh_flag == "host" and not (len(spread) == len(devices)
                                        and split):
            raise AssertionError("sharded serving left the parameters on "
                                 f"{len(spread)} device(s)")
        del server
    if streams["none"] != streams["host"]:
        first = [next((i for i, (a, b) in enumerate(zip(x, y)) if a != b),
                      None) for x, y in zip(streams["none"], streams["host"])]
        raise AssertionError("sharded and unsharded greedy streams differ; "
                             f"first differing token per request: {first}")
    print(f"[4] sharded and unsharded greedy streams equal "
          f"({len(streams['host'])} requests)", flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the mesh-sharded serving phase on a "
                         "four-chip host")
    opts = ap.parse_args(argv)
    if opts.four_chip:
        # Equal streams need equal arithmetic. The sharded and unsharded
        # steps fuse differently, and by default XLA may keep a fused bf16
        # intermediate in f32, so the two programs would round at different
        # places; at random weights one rounding apart is enough to change
        # the CIM model's greedy stream (PERF.md, Findings). Set
        # before JAX starts its backend, which reads XLA_FLAGS once.
        os.environ["XLA_FLAGS"] = " ".join(filter(None, (
            os.environ.get("XLA_FLAGS"),
            "--xla_allow_excess_precision=false")))

    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    import jax
    from repro.core.engine import _force_jnp
    from repro.launch import serve
    from repro.models import registry
    if _force_jnp():
        sys.exit("chip_smoke: REPRO_FORCE_JNP is set, which replaces every "
                 "kernel with jnp; unset it")
    devices = jax.devices()
    platform = devices[0].platform
    if platform != "tpu":
        sys.exit(f"chip_smoke: needs a TPU, JAX found {platform!r}")
    if opts.four_chip and len(devices) != 4:
        sys.exit(f"chip_smoke --four-chip: needs 4 chips, found "
                 f"{len(devices)}")
    kind = devices[0].device_kind
    print(f"device: {kind} x{len(devices)}, jax {jax.__version__}",
          flush=True)

    args = serve.build_parser().parse_args(list(PREQUANT_FLAGS))
    t0 = time.perf_counter()
    params = registry.init_params(jax.random.PRNGKey(0),
                                  serve.model_config(args),
                                  max_seq=args.max_len)
    jax.block_until_ready(params)
    print(f"init internlm2-1.8b params (seed 0): "
          f"{time.perf_counter() - t0:.1f}s; {_memory(devices[0])}",
          flush=True)
    if opts.four_chip:
        phase_four_chip(PREQUANT_FLAGS, params)
    else:
        phase_kernels()
        phase_serve(PREQUANT_FLAGS, params)
        phase_noisy(NOISY_FLAGS, params)
    print(json.dumps({"ok": True, "device": {
        "platform": platform, "kind": kind, "count": len(devices)}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
