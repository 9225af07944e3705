"""cim_mvm Pallas kernel micro-bench: interpret-mode wall time vs the jnp
reference across tile shapes (structural check — real perf is a TPU matter,
the §Perf roofline reasons from the lowered IR), a packed-vs-unpacked
decode-shape sweep quantifying the nibble-packing HBM win, a stochastic
(NOISY) fused-kernel sweep checking the in-kernel PRNG's distributional
agreement with the einsum reference, a PAGED-ATTENTION sweep (schema v3:
the Pallas flash kernel vs the exact window-softmax reference across
window lengths, with the peak score-tensor byte probe — exact grows as
O(W), the kernel's live scores stay one O(block) tile), and a SERVING
sweep driving the runtime.server engines (paged vs slot cache, plus the
paged engine on the kernel attention backend) over concurrent requests
with mixed prompt lengths — decode tok/s plus the resident KV-cache bytes
at 25 % slot occupancy (the paged-pool memory win).

Schema v4 adds the AUTOTUNE sweep: `--autotune` times every candidate
pipeline config from kernels.autotune (paged-attention kblocks/row_tile,
CIM-MVM (bm, bn) tiles) through this same harness, reports paired
`<name>_default` / `<name>_tuned` rows (the tuned row's derived field
carries `default_us` and `speedup`), and persists the winners as a
tune-cache JSON (`--tune-cache`, default tune_cache.json) that the
dispatchers consult through $REPRO_TUNE_CACHE.

Schema v5 adds the SHARED-PREFIX serving row: requests with a common
prompt prefix drained through the prefix-sharing paged pool vs the same
pool with sharing disabled — sustained decode concurrency (peak lanes
past prefill in one step, the pool-capacity-limited number) and the
prefill tokens the trie absorbed. The bench-smoke CI job gates the
concurrency ratio > 5x.

Schema v6 adds the SPEC-DECODE serving row: the same greedy workload
drained through the paged engine twice — plain decode vs speculative
decode with the ngram drafter (spec_k drafts verified per C=k+1 step) —
after a full warm-up drain per leg so every compiled step shape is
resident before timing. Reports decode tok/s per leg, the speedup (the
number the bench-smoke CI job gates ≥ 1.5x), the accept rate / mean
accepted length, and the accept-length histogram; asserts the two legs
emit bit-identical tokens (the greedy-parity invariant the soak tests
pin).

Schema v7 adds the ENERGY-PARETO row: the mixed-precision autotuner
(analysis.precision_search) searches per-call-site (ADC levels, scheme,
per-channel) overrides on the calibration tree and reports serving
energy/token — uniform 4b×4b BP at native ADC resolution vs the searched
mixed manifest — plus the accuracy-proxy delta (held-out logit KL vs the
float reference, uniform vs mixed). The bench-smoke CI job gates the
mixed-precision energy win ≥ 1.3x at iso-proxy and uploads the manifest
(`--precision-manifest`, consumed by serve.py / ServingConfig) as an
artifact.

Schema v8 adds the SERVE-SLO row: a mixed-prompt workload drained through
the paged engine twice — telemetry on (runtime.telemetry event trace +
step snapshots + histograms) vs telemetry off — with a full warm-up drain
and best-of-N timed repeats per leg. Reports p50/p99 TTFT and ITL from
the telemetry histograms plus decode tok/s per leg and the telemetry
overhead percentage; the bench-smoke CI job gates overhead < 3 %.

CLI (the CI bench-smoke job):
    PYTHONPATH=src python -m benchmarks.kernel_bench --small \\
        --autotune --json-out BENCH_ci.json
writes a machine-readable BENCH_ci.json ({"schema": ..., "rows": [...]})
so per-PR perf-trajectory data accumulates as workflow artifacts."""
import argparse
import dataclasses
import json

import jax
import jax.numpy as jnp

from repro.core.macro import MacroConfig, SimLevel
from repro.core.schemes import cim_mvm_codes
from repro.kernels import autotune
from repro.kernels.ops import (cim_mvm_pallas, cim_mvm_pallas_noisy,
                               cim_mvm_pallas_packed, pack_codes)
from repro.kernels.ref import cim_mvm_ref

from .common import row, timeit

BENCH_SCHEMA = "pico-ram/kernel_bench/v8"  # v8: + serve-SLO telemetry row


def run(small: bool = False, precision_manifest: str | None = None):
    out = []
    cfg = MacroConfig()
    key = jax.random.PRNGKey(0)
    # --small: one macro group deep, one tile — the CI smoke configuration
    m, k, n = (64, 288, 64) if small else (256, 1152, 256)
    x = jax.random.randint(key, (m, k), 0, 16).astype(jnp.float32)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), 0,
                           16).astype(jnp.float32)

    ref = jax.jit(lambda a, b: cim_mvm_ref(a, b, n_rows=cfg.n_rows,
                                           levels=cfg.adc_levels,
                                           gain=cfg.gain,
                                           full_scale=cfg.full_scale()))
    us_ref = timeit(ref, x, w)
    out.append(row(f"kernel_ref_jnp_{k}x{n}", us_ref, "oracle"))
    tiles = ((64, 64),) if small else ((64, 64), (128, 128), (256, 256))
    for bm, bn in tiles:
        fn = lambda a, b: cim_mvm_pallas(a, b, cfg, bm=bm, bn=bn)
        us = timeit(fn, x, w)
        out.append(row(f"kernel_pallas_bm{bm}_bn{bn}", us,
                       f"interpret_mode|vs_ref={us / max(us_ref, 1e-9):.2f}x"))
    out += run_noisy_sweep(small)
    out += run_packed_sweep(small)
    out += run_paged_attention_sweep(small)
    out += run_serving_sweep(small)
    out += run_shared_prefix_sweep(small)
    out += run_spec_decode_sweep(small)
    out += run_serve_slo_sweep(small)
    out += run_energy_pareto(small, manifest_out=precision_manifest)
    return out


def run_energy_pareto(small: bool = False,
                      manifest_out: str | None = None):
    """Mixed-precision serving energy: uniform vs the searched manifest.

    Runs the full autotuner loop on the LM smoke (calibration tree →
    greedy per-site (ADC levels, scheme, per-channel) descent under the
    SQNR screen + held-out logit-KL budget) and reports the Eq. 4 serving
    energy/token of the uniform native-resolution baseline against the
    mixed config, at iso-accuracy-proxy (both KLs vs the FLOAT reference
    in the derived field — the mixed config may drift at most kl_budget
    beyond uniform). The search is fully deterministic (fixed seed), so
    this row is a stable trend like every other bench row. The winning
    manifest — the deployment artifact ServingConfig(precision_manifest=)
    consumes — is written to `manifest_out`.
    """
    import time

    import numpy as np

    from repro.analysis import precision_search as ps
    from repro.configs.registry import SMOKES
    from repro.core.cim_matmul import CIMConfig
    from repro.models import registry as model_registry

    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32",
                                           cim=CIMConfig(enabled=True))
    params = model_registry.init_params(jax.random.PRNGKey(0), cfg,
                                        max_seq=64)
    cal = np.random.RandomState(7).randint(0, cfg.vocab, size=(2, 16))
    t0 = time.perf_counter()
    man = ps.search(params, cal, cfg, seed=0)
    search_us = (time.perf_counter() - t0) * 1e6
    if manifest_out:
        ps.save_manifest(manifest_out, man)
    m = man["metrics"]
    levels = ";".join(f"{k}:{v['adc_levels']}"
                      for k, v in man["sites"].items())
    return [row(
        "energy_pareto_mixed_precision", search_us,
        f"uniform_pj_tok={m['uniform_pj_per_token']:.1f}|"
        f"mixed_pj_tok={m['mixed_pj_per_token']:.1f}|"
        f"energy_win={m['energy_win']:.3f}x|"
        f"kl_uniform={m['kl_uniform']:.4f}|kl_mixed={m['kl_proxy']:.4f}|"
        f"kl_budget={m['kl_budget']:.3f}|levels={levels}")]


def run_paged_attention_sweep(small: bool = False):
    """Pallas paged-attention kernel vs the exact window-softmax reference.

    Decode-shaped (C=1) attention over a paged block pool through per-slot
    block tables, swept over window lengths. Two numbers per window:

      * wall µs, kernel vs exact (interpret-mode on CPU CI — a structural
        trend like the other kernel rows);
      * the peak score-tensor bytes — the memory probe the kernel exists
        for. The exact path materializes the [B, C, KH, G, W] score tensor
        (grows linearly with the window); the kernel's live scores are one
        [C·G, block_size] VMEM tile per program, CONSTANT in W. Exact
        byte counts, platform-free.
    """
    from repro.kernels.paged_attention import get_attn_backend
    out = []
    b, kh, g, dh, bs = 2, 2, 2, 32, 8
    windows = (64, 256) if small else (256, 1024, 4096)
    key = jax.random.PRNGKey(5)
    for w in windows:
        mb = w // bs
        nb = b * mb + 1              # every slot fully backed + trash block
        q = jax.random.normal(key, (b, 1, kh * g, dh), jnp.float32)
        kp = jax.random.normal(jax.random.fold_in(key, w),
                               (nb, kh, bs, dh), jnp.float32)
        vp = jax.random.normal(jax.random.fold_in(key, w + 1),
                               (nb, kh, bs, dh), jnp.float32)
        tables = (1 + jnp.arange(b * mb, dtype=jnp.int32)).reshape(b, mb)
        lens = jnp.full((b,), w - 1, jnp.int32)     # full-depth decode
        positions = lens[:, None]
        kvl = lens + 1

        def run_backend(name):
            fn = get_attn_backend(name).fn
            return jax.jit(lambda q, k, v: fn(q, k, v, tables, positions,
                                              kvl))

        us_e = timeit(run_backend("exact"), q, kp, vp)
        us_k = timeit(run_backend("kernel"), q, kp, vp)
        bytes_exact = b * 1 * kh * g * w * 4
        bytes_kernel = 1 * g * bs * 4
        out.append(row(
            f"paged_attn_decode_w{w}", us_k,
            f"exact_us={us_e:.1f}|score_bytes exact={bytes_exact} "
            f"kernel={bytes_kernel} "
            f"({bytes_exact / bytes_kernel:.0f}x less)"))
    return out


def run_noisy_sweep(small: bool = False):
    """Stochastic fused kernel vs the einsum NOISY reference: wall time plus
    the distributional-agreement ratio (σ of the ADC-chain error, fused
    in-kernel PRNG vs jax.random.normal) — the number the engine tests pin,
    tracked here per-PR so a PRNG regression shows up in the artifact."""
    out = []
    cfg = dataclasses.replace(MacroConfig(), sim_level=SimLevel.NOISY)
    ideal = dataclasses.replace(cfg, sim_level=SimLevel.IDEAL)
    key = jax.random.PRNGKey(3)
    m, k, n = (32, 288, 64) if small else (64, 1152, 256)
    x = jax.random.randint(key, (m, k), 0, 16).astype(jnp.float32)
    w = jax.random.randint(jax.random.fold_in(key, 1), (k, n), 0,
                           16).astype(jnp.float32)
    us_f = timeit(lambda a, b: cim_mvm_pallas_noisy(a, b, cfg, noise_seed=0),
                  x, w)
    us_e = timeit(jax.jit(lambda a, b, kk: cim_mvm_codes(a, b, cfg, key=kk)),
                  x, w, jax.random.fold_in(key, 2))
    y_ideal = cim_mvm_pallas(x, w, ideal)
    s_f = float(jnp.std(cim_mvm_pallas_noisy(x, w, cfg, noise_seed=0)
                        - y_ideal))
    s_e = float(jnp.std(cim_mvm_codes(x, w, cfg,
                                      key=jax.random.fold_in(key, 2))
                        - y_ideal))
    out.append(row(
        f"kernel_pallas_noisy_m{m}_k{k}_n{n}", us_f,
        f"einsum_noisy_us={us_e:.1f}|err_sigma fused={s_f:.3f} "
        f"einsum={s_e:.3f} ratio={s_f / max(s_e, 1e-9):.3f}"))
    return out


def run_packed_sweep(small: bool = False):
    """Packed vs unpacked weights across decode shapes (small M = batch
    slots, big K×N = the weight matrix that dominates decode HBM traffic).

    Decode is memory-bound: the roofline weight-byte term is exact
    (K·N bytes int8 vs ceil(K/2)·N bytes packed = 2.00× less wire traffic,
    4× vs bf16). Wall time here is interpret-mode (structural); the
    bytes ratio is the production-relevant number and is reported per
    shape."""
    out = []
    cfg = MacroConfig()
    key = jax.random.PRNGKey(2)
    shapes = ((8, 576, 128),) if small \
        else ((8, 1152, 512), (8, 2304, 2048), (32, 4320, 1024))
    for m, k, n in shapes:
        x = jax.random.randint(key, (m, k), 0, 16).astype(jnp.float32)
        w = jax.random.randint(jax.random.fold_in(key, k + n), (k, n), 0,
                               16).astype(jnp.float32)
        wp = pack_codes(w)
        us_u = timeit(lambda a, b: cim_mvm_pallas(a, b, cfg), x, w)
        us_p = timeit(lambda a, b: cim_mvm_pallas_packed(a, b, cfg), x, wp)
        bytes_u = k * n                    # int8 container codes
        bytes_p = wp.shape[0] * n          # two u4 codes per byte
        out.append(row(
            f"decode_packed_m{m}_k{k}_n{n}", us_p,
            f"unpacked_us={us_u:.1f}|w_bytes {bytes_u}->{bytes_p} "
            f"({bytes_u / bytes_p:.2f}x less HBM)"))
    return out


def run_serving_sweep(small: bool = False):
    """Continuous-batching server sweep: paged vs slot engines end to end.

    Concurrent requests with mixed (seeded) prompt lengths drain through
    both runtime.server engines on the smoke transformer. Reported:

      * decode tok/s per engine (interpret/CPU wall clock — a structural
        trend like the kernel rows, not TPU absolute perf); the paged
        engine is drained twice, once per attention backend, so the
        kernel-vs-exact serving ratio lands in the artifact;
      * resident KV-cache bytes at 25 % slot occupancy: the slot cache
        always holds n_slots × max_len positions, the paged pool only the
        blocks its admitted requests actually cached — the exact byte
        counts are platform-free and are the paged-engine win the trend
        pipeline tracks.
    """
    from repro.configs.registry import SMOKES
    from repro.models import registry as model_registry
    from repro.runtime.server import Request, Server, ServingConfig

    out = []
    import numpy as np
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    n_slots, max_len, block = (4, 64, 8) if small else (8, 128, 16)
    n_req, max_new = (4, 4) if small else (12, 8)
    params = model_registry.init_params(jax.random.PRNGKey(0), cfg,
                                        max_seq=max_len)
    rng = np.random.RandomState(11)
    plens = [int(rng.randint(3, max_len // 4)) for _ in range(n_req)]
    prompts = [rng.randint(0, cfg.vocab, size=p).tolist() for p in plens]

    def drain(paged: bool, attn: str = "exact") -> Server:
        # attention backend pinned explicitly so each row's meaning is
        # stable across PRs (auto re-resolving would silently rebase the
        # paged trend onto the kernel path)
        srv = Server(params, cfg, ServingConfig(
            n_slots=n_slots, max_len=max_len, paged=paged, block_size=block,
            prefill_chunk=max_len // 8, attn=attn))
        for p in prompts:
            srv.submit(Request(prompt=list(p), max_new_tokens=max_new))
        srv.run_until_drained()
        return srv

    slot_bytes = 0
    exact_tok_s = 0.0
    for paged in (False, True):
        srv = drain(paged)
        m = srv.metrics.summary()
        name = "paged" if paged else "slots"
        us_per_tok = m["wall_s"] * 1e6 / max(m["decode_tokens"], 1)
        out.append(row(
            f"serve_decode_{name}_s{n_slots}_r{n_req}", us_per_tok,
            f"decode_tok_s={m['decode_tok_s']:.1f}|"
            f"prefill_tok_s={m['prefill_tok_s']:.1f}|steps={m['steps']}"))
        if not paged:
            slot_bytes = srv.kv_cache_bytes()["total"]
        else:
            exact_tok_s = m["decode_tok_s"]

    # the same paged drain on the Pallas attention kernel: the serving-level
    # kernel-vs-exact decode tok/s the acceptance criteria track
    srv = drain(True, attn="kernel")
    m = srv.metrics.summary()
    us_per_tok = m["wall_s"] * 1e6 / max(m["decode_tokens"], 1)
    out.append(row(
        f"serve_decode_paged_attnkernel_s{n_slots}_r{n_req}", us_per_tok,
        f"decode_tok_s={m['decode_tok_s']:.1f}|"
        f"exact_tok_s={exact_tok_s:.1f}|"
        f"ratio={m['decode_tok_s'] / max(exact_tok_s, 1e-9):.3f}"))

    # KV residency at 25 % slot occupancy: drain ceil(slots/4) requests
    # through the paged engine and report its PEAK block residency (robust
    # to schedule changes, unlike a mid-flight snapshot) vs the slot
    # cache's always-resident n_slots × max_len footprint.
    occ = max(1, n_slots // 4)
    srv = Server(params, cfg, ServingConfig(
        n_slots=n_slots, max_len=max_len, paged=True, block_size=block,
        prefill_chunk=max_len // 8))
    for p in prompts[:occ]:
        srv.submit(Request(prompt=list(p), max_new_tokens=max_new))
    srv.run_until_drained()
    per_block = srv.kv_cache_bytes()["total"] \
        // (srv.alloc.stats.num_blocks + 1)
    paged_bytes = per_block * srv.alloc.stats.peak_in_use
    assert paged_bytes > 0, "occupancy probe allocated no blocks"
    t_probe = srv.metrics.wall_s * 1e6
    out.append(row(
        f"serve_kv_bytes_occ25_s{n_slots}", max(t_probe, 1e-3),
        f"kv_bytes slot={slot_bytes} paged={paged_bytes} "
        f"({slot_bytes / paged_bytes:.2f}x less HBM)"))
    return out


def run_shared_prefix_sweep(small: bool = False):
    """Prefix-sharing paged pool vs the same pool with sharing disabled.

    One warm request populates the prefix trie with a 48-token shared
    prompt prefix (6 blocks at block_size 8), then n_req followers with the
    same prefix + distinct 2-token tails drain together through a pool
    sized so ONE private request fits but two do not (13 usable blocks;
    each request spans 7). Reported, per leg:

      * peak decode lanes — the max lanes simultaneously PAST prefill in a
        single step. Unlike admitted-lane counts (optimistic watermark
        admission transiently over-admits in both legs before preemption
        corrects it), a lane in decode provably holds all its blocks, so
        this is the pool-capacity-limited concurrency. Sharing backs each
        follower with 1 private block + 6 trie blocks → all n_req decode
        together; without sharing two full residents exceed the pool → 1;
      * prefill tokens absorbed by the trie (48 × n_req when sharing);
      * preemptions — 0 when sharing, a storm without.

    The bench-smoke CI job gates shared/nosharing peak decode lanes > 5x:
    the concurrency win the refcounted CoW pool exists for. Deterministic
    (greedy decode, exact counts), so the gate is noise-free.
    """
    from repro.configs.registry import SMOKES
    from repro.models import registry as model_registry
    from repro.runtime.server import Request, Server, ServingConfig

    import numpy as np
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    bs, max_len, n_slots, num_blocks = 8, 64, 8, 13
    n_req, max_new, shared_len = 7, 4, 48
    rng = np.random.RandomState(17)
    prefix = rng.randint(0, cfg.vocab, size=shared_len).tolist()
    tails = [rng.randint(0, cfg.vocab, size=2).tolist()
             for _ in range(n_req + 1)]
    params = model_registry.init_params(jax.random.PRNGKey(0), cfg,
                                        max_seq=max_len)

    def drain(sharing: bool) -> Server:
        srv = Server(params, cfg, ServingConfig(
            n_slots=n_slots, max_len=max_len, paged=True, block_size=bs,
            num_blocks=num_blocks, prefill_chunk=bs, attn="exact",
            prefix_sharing=sharing))
        srv.submit(Request(prompt=prefix + tails[0],
                           max_new_tokens=max_new))
        srv.run_until_drained()          # warm: populates the trie
        srv.metrics = type(srv.metrics)()  # measure followers only
        for t in tails[1:]:
            srv.submit(Request(prompt=prefix + t, max_new_tokens=max_new))
        srv.run_until_drained()
        return srv

    shared = drain(True)
    base = drain(False)
    ms, mb = shared.metrics, base.metrics
    ratio = ms.peak_decode_lanes / max(mb.peak_decode_lanes, 1)
    return [row(
        f"serve_shared_prefix_s{n_slots}_r{n_req}",
        max(ms.wall_s * 1e6, 1e-3),
        f"peak_lanes shared={ms.peak_decode_lanes} "
        f"nosharing={mb.peak_decode_lanes} ({ratio:.1f}x)|"
        f"prefill_tok_saved={ms.prefix_hit_tokens}|"
        f"preempt shared={ms.preemptions} nosharing={mb.preemptions}")]


def run_spec_decode_sweep(small: bool = False):
    """Speculative vs plain greedy decode on the paged engine.

    The same two seeded prompts drain through the paged engine twice:
    plain decode (one token per step) and speculative decode with the
    ngram drafter (spec_k drafts verified in one C=spec_k+1 all-logits
    step, longest agreeing prefix accepted, rollback = truncating the
    lane's kv_len). Long greedy generations on the random-weight smoke
    model reach (near-)periodic attractors, which is exactly the regime
    prompt-lookup drafting exploits — so the accept rate here is a
    stable, deterministic property of the seeds, not noise.

    Methodology: each leg drains the identical workload ONCE un-timed
    (compiles every step shape: prefill chunk, plain C=1, spec C=k+1
    all-logits), resets metrics, then drains again timed — the reported
    tok/s is steady-state serving, not XLA compile time. prefill_chunk
    is pinned to spec_k+1 so both phases share one compiled width.

    Reported: decode tok/s per leg, speedup (bench-smoke CI gates
    ≥ 1.5x), accept rate, mean accepted length, accept-length histogram.
    Asserts both legs emit bit-identical tokens — the greedy-parity
    invariant (exact verification ⇒ spec decode is a pure perf knob).
    """
    from repro.configs.registry import SMOKES
    from repro.models import registry as model_registry
    from repro.runtime.server import Request, Server, ServingConfig

    import numpy as np
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    spec_k = 4
    max_len, max_new = 256, 160
    n_slots, block = 2, 16
    # seeds chosen for their attractor structure: both prompts' greedy
    # continuations go (near-)periodic well inside max_new, the regime
    # the paper-adjacent prompt-lookup literature targets
    prompts = []
    for seed in (7, 23):
        r = np.random.RandomState(seed)
        prompts.append(
            r.randint(0, cfg.vocab, size=int(r.randint(4, 17))).tolist())
    params = model_registry.init_params(jax.random.PRNGKey(0), cfg,
                                        max_seq=max_len)

    def drain(drafter: str) -> tuple[Server, list[list[int]], float]:
        srv = Server(params, cfg, ServingConfig(
            n_slots=n_slots, max_len=max_len, paged=True, block_size=block,
            prefill_chunk=spec_k + 1, attn="exact",
            drafter=drafter, spec_k=spec_k))

        def once() -> list[list[int]]:
            reqs = [Request(prompt=list(p), max_new_tokens=max_new)
                    for p in prompts]
            for r in reqs:
                srv.submit(r)
            srv.run_until_drained()
            return [list(r.output) for r in reqs]

        once()                              # warm: compile every step shape
        srv.metrics = type(srv.metrics)()   # timed leg starts clean
        outs = once()
        return srv, outs, srv.metrics.summary()["decode_tok_s"]

    _, plain_out, plain_tok_s = drain("off")
    srv, spec_out, spec_tok_s = drain("ngram")
    assert plain_out == spec_out, \
        "greedy spec decode diverged from plain decode"
    m = srv.metrics.summary()
    hist = ";".join(f"{k}:{v}" for k, v in m["accept_hist"].items())
    return [row(
        f"serve_spec_decode_k{spec_k}_s{n_slots}",
        m["wall_s"] * 1e6 / max(m["decode_tokens"], 1),
        f"spec_tok_s={spec_tok_s:.1f}|plain_tok_s={plain_tok_s:.1f}|"
        f"speedup={spec_tok_s / max(plain_tok_s, 1e-9):.2f}x|"
        f"accept_rate={m['accept_rate']:.2f}|"
        f"mean_accept_len={m['mean_accept_len']:.2f}|hist={hist}")]


def run_serve_slo_sweep(small: bool = False):
    """Serving SLO percentiles + the telemetry overhead contract.

    One mixed-prompt greedy workload drains through the paged engine in
    two configurations that differ ONLY in ServingConfig.telemetry: the
    on-leg populates the runtime.telemetry event trace / step snapshots /
    TTFT+ITL histograms, the off-leg early-returns at every hook. Each
    leg warms once un-timed (compiles every step shape), then runs
    best-of-N timed drains (metrics reset per repeat, repeats
    interleaved with alternating order so machine-level drift hits both
    legs equally).

    Reported: p50/p99 TTFT and ITL in ms from the on-leg's histograms
    (accumulated across the timed repeats — more samples, stabler tails;
    the warm drain's compile-poisoned samples are reset out), decode
    tok/s per leg (best-of-N drains), and the overhead percentage the
    bench-smoke CI job gates < 3 %.

    How the gated overhead is measured — DIRECT ATTRIBUTION, not the
    on/off throughput difference.  The on-leg's telemetry hooks are
    wrapped with perf_counter pairs and the gate is the median (across
    repeats) of ``time inside hooks / total step() wall``.  Rationale,
    from calibrating on shared CI-class hosts: the differential
    estimate is swamped by noise the hooks don't cause.  Two servers
    built identically WITH TELEMETRY OFF measure 1-2 % apart with
    persistent per-step-index wall differences of +-10 % (each instance
    jits its own step functions, so code/memory placement differs), and
    noisy-neighbor steal adds multi-percent swings that survive
    interleaving, per-step-index min-pairing over dozens of repeats,
    and median-of-phases — while the true hook cost is ~1 % of a step.
    A hard gate on a differential below its own noise floor flakes; the
    attributed fraction is a within-run ratio, so host slowdowns scale
    numerator and denominator together.  It is also conservative where
    it matters: each wrapped call pays the timer overhead inside the
    numerator, and a regression that fattens the hooks (say,
    reintroducing per-lane ring appends on the decode path) lands on it
    directly.  What it cannot see is indirect cost (GC pressure from
    ring allocations, cache pollution), so the on/off tok/s pair stays
    in the derived field as the end-to-end cross-check: tok_s_on within
    noise of tok_s_off is the claim a human should eyeball, and both
    numbers are best-of-N under one-sided noise (a neighbor only ever
    slows a run down).

    The gated fraction is the telemetry HOT phase: Telemetry's hooks
    append raw tuples and defer aggregation (Event/ring/histogram work)
    to a replay pass that runs at read time, outside the step walls —
    see the Telemetry class docstring.  The replay cost is real but
    off-SLO-path by design; the TTFT/ITL percentiles above come from
    the same drains and would show it if it leaked into serving.
    """
    import time

    from repro.configs.registry import SMOKES
    from repro.models import registry as model_registry
    from repro.runtime.server import Request, Server, ServingConfig

    import numpy as np
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    n_slots, max_len, block = (4, 64, 8) if small else (8, 128, 16)
    n_req, max_new = (6, 8) if small else (12, 16)
    # drains are tens of ms; lots of interleaved repeats cost little and
    # best-of-N converges on true capability under one-sided timing noise
    # (CI neighbors only ever make a run SLOWER)
    repeats = 9 if small else 5
    rng = np.random.RandomState(29)
    prompts = [rng.randint(0, cfg.vocab,
                           size=int(rng.randint(4, max_len // 4))).tolist()
               for _ in range(n_req)]
    params = model_registry.init_params(jax.random.PRNGKey(0), cfg,
                                        max_seq=max_len)

    def build(telemetry_on: bool) -> Server:
        return Server(params, cfg, ServingConfig(
            n_slots=n_slots, max_len=max_len, paged=True, block_size=block,
            prefill_chunk=max_len // 8, attn="exact",
            telemetry=telemetry_on))

    # every recording entry point the Server calls (event() is the shared
    # internal path of several of these — wrapping it too would double
    # count); telemetry.now() is deliberately unwrapped, both legs pay it
    hooks = ("submit", "admit", "prefill_chunk", "first_token", "emission",
             "decode_step", "spec_verify", "cow_fork", "preempt", "retire",
             "step_snapshot")

    def instrument(tel) -> list:
        """Shadow each hook on the INSTANCE with a self-timing wrapper."""
        acc = [0.0]
        for name in hooks:
            base = getattr(tel, name)

            def timed(*a, _base=base, _acc=acc, **kw):
                t0 = time.perf_counter()
                r = _base(*a, **kw)
                _acc[0] += time.perf_counter() - t0
                return r

            setattr(tel, name, timed)
        return acc

    def once(srv: Server) -> tuple:
        srv.metrics = type(srv.metrics)()       # timed repeats start clean
        for p in prompts:
            srv.submit(Request(prompt=list(p), max_new_tokens=max_new))
        wall = 0.0
        while any(srv.slot_req) or srv.queue:   # run_until_drained, but
            t0 = time.perf_counter()            # timing each step() wall
            srv.step()
            wall += time.perf_counter() - t0
        return srv.metrics.summary()["decode_tok_s"], wall

    srv_on, srv_off = build(True), build(False)
    hook_s = instrument(srv_on.telemetry)
    once(srv_on)                                # warm: compile every shape
    once(srv_off)
    srv_on.telemetry.reset()                    # drop compile-poisoned TTFTs
    tok_s_on = tok_s_off = 0.0
    ratios = []                                 # per-drain hook_s / step wall
    for r in range(repeats):        # interleave, alternate leg order — see
        legs = ("on", "off") if r % 2 == 0 else ("off", "on")   # docstring
        for leg in legs:
            if leg == "on":
                hook_s[0] = 0.0
                tok, wall = once(srv_on)
                tok_s_on = max(tok_s_on, tok)
                ratios.append(hook_s[0] / wall)
            else:
                tok, _ = once(srv_off)
                tok_s_off = max(tok_s_off, tok)
    overhead = sorted(ratios)[len(ratios) // 2] * 100.0
    tel = srv_on.telemetry
    m = srv_on.metrics.summary()
    return [row(
        f"serve_slo_paged_s{n_slots}_r{n_req}",
        m["wall_s"] * 1e6 / max(m["decode_tokens"], 1),
        f"ttft_p50_ms={tel.ttft.percentile(50) * 1e3:.2f}|"
        f"ttft_p99_ms={tel.ttft.percentile(99) * 1e3:.2f}|"
        f"itl_p50_ms={tel.itl.percentile(50) * 1e3:.2f}|"
        f"itl_p99_ms={tel.itl.percentile(99) * 1e3:.2f}|"
        f"tok_s_on={tok_s_on:.1f}|tok_s_off={tok_s_off:.1f}|"
        f"overhead_pct={overhead:+.2f}")]


def run_autotune(small: bool = False):
    """Time every candidate config from kernels.autotune and keep the wins.

    Two shape families, chosen to be the ones the acceptance criteria
    track:

      * paged attention, decode at W = 4096 (`decode_w4096`) — the window
        where the default pagination pays 256 sequential fetch steps. The
        candidate space is (block_size, kblocks, row_tile): kblocks fetches
        several blocks per step (the TPU double-buffering win), block_size
        re-paginates the pool into coarser blocks (fewer, larger fetches —
        the win that also shows in interpret mode, where per-fetch overhead
        dominates). Run even under --small: the family IS the artifact row.
      * the CIM MVM tile family of the --small smoke shape (m64_g2_n64) or
        the full bench shape, over (bm, bn) tile candidates.

    Returns (rows, entries): paired `_default`/`_tuned` bench rows plus the
    tune-cache entries for autotune.save_cache. The default config is
    always candidate 0, so `_tuned` can only tie or beat it.
    """
    from repro.kernels.paged_attention import paged_flash_attention
    rows_out, entries = [], {}

    # ---- paged attention: decode, W = 4096 --------------------------------
    # candidate 0 is the serving default (block_size 16, kblocks 1); the
    # block_size candidates re-paginate the SAME window into coarser pool
    # blocks — fewer, larger fetches per sequential step, the layout knob
    # serve.py --tune-cache feeds back into the paged pool
    b, kh, g, dh, bs = 1, 1, 4, 32, 16
    w = 4096
    key = jax.random.PRNGKey(9)
    q = jax.random.normal(key, (b, 1, kh * g, dh), jnp.float32)
    kv = jax.random.normal(jax.random.fold_in(key, 1),
                           (2, b, w, kh, dh), jnp.float32)
    cands = autotune.attn_candidates(w // bs, kh * g, block_size=bs)
    if small:  # smoke: default, deepest pipeline, and the layout candidates
        cands = [c for c in cands
                 if c["kblocks"] in (1, 16) or c["block_size"] != bs]
    timed = []
    for cand in cands:
        cbs = cand["block_size"]
        mb = w // cbs
        pools = kv.reshape(2, b * mb, cbs, kh, dh).swapaxes(2, 3)
        kp = jnp.concatenate([jnp.zeros((1, kh, cbs, dh)), pools[0]])
        vp = jnp.concatenate([jnp.zeros((1, kh, cbs, dh)), pools[1]])
        tables = (1 + jnp.arange(b * mb, dtype=jnp.int32)).reshape(b, mb)
        lens = jnp.full((b,), w - 1, jnp.int32)
        kvl = lens + 1
        fn = jax.jit(lambda qq, kk, vv, _t=tables, _l=lens, _kv=kvl,
                     _kb=cand["kblocks"], _rt=cand["row_tile"]:
                     paged_flash_attention(qq, kk, vv, _t, _l, _kv,
                                           kblocks=_kb, row_tile=_rt))
        timed.append((timeit(fn, q, kp, vp), cand))
    default_us = timed[0][0]
    best_us, best = min(timed, key=lambda t: t[0])
    fam = autotune.attn_family(w, 1)
    entries[autotune.cache_key("paged_attn", fam, "kernel")] = {
        **best, "us": best_us, "default_us": default_us}
    rows_out.append(row(f"paged_attn_{fam}_default", default_us,
                        f"block_size={bs}|kblocks=1|row_tile=None"))
    rows_out.append(row(
        f"paged_attn_{fam}_tuned", best_us,
        f"default_us={default_us:.1f}|"
        f"speedup={default_us / max(best_us, 1e-9):.2f}x|"
        f"block_size={best['block_size']}|"
        f"kblocks={best['kblocks']}|row_tile={best['row_tile']}"))

    # ---- CIM MVM tiles ----------------------------------------------------
    cfg = MacroConfig()
    m, k, n = (64, 288, 64) if small else (256, 1152, 256)
    x = jax.random.randint(key, (m, k), 0, 16).astype(jnp.float32)
    wmat = jax.random.randint(jax.random.fold_in(key, 3), (k, n), 0,
                              16).astype(jnp.float32)
    timed = []
    for cand in autotune.mvm_candidates(m, n):
        fn = (lambda a, bb, _bm=cand["bm"], _bn=cand["bn"]:
              cim_mvm_pallas(a, bb, cfg, bm=_bm, bn=_bn))
        timed.append((timeit(fn, x, wmat), cand))
    default_us = timed[0][0]
    best_us, best = min(timed, key=lambda t: t[0])
    fam = autotune.mvm_family(m, -(-k // cfg.n_rows), n)
    entries[autotune.cache_key("cim_mvm", fam, "pallas")] = {
        **best, "us": best_us, "default_us": default_us}
    rows_out.append(row(f"cim_mvm_{fam}_default", default_us,
                        "bm=128|bn=128"))
    rows_out.append(row(
        f"cim_mvm_{fam}_tuned", best_us,
        f"default_us={default_us:.1f}|"
        f"speedup={default_us / max(best_us, 1e-9):.2f}x|"
        f"bm={best['bm']}|bn={best['bn']}"))
    return rows_out, entries


def rows_to_json(rows: list[str]) -> dict:
    """CSV rows ("name,us,derived") → the BENCH_ci.json document."""
    parsed = []
    for line in rows:
        name, us, derived = line.split(",", 2)
        parsed.append({"name": name, "us": float(us), "derived": derived})
    return {
        "schema": BENCH_SCHEMA,
        "jax": jax.__version__,
        "backend": jax.default_backend(),
        "rows": parsed,
    }


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--small", action="store_true",
                    help="CI smoke configuration (one group deep, one tile)")
    ap.add_argument("--json-out", default=None, metavar="PATH",
                    help="also write the rows as a JSON document "
                         "(the bench-smoke artifact)")
    ap.add_argument("--autotune", action="store_true",
                    help="time the kernels.autotune candidate configs, "
                         "append tuned-vs-default rows, and persist the "
                         "winners to --tune-cache")
    ap.add_argument("--tune-cache", default="tune_cache.json",
                    metavar="PATH",
                    help="where --autotune writes the tuning cache "
                         "(consumed via $REPRO_TUNE_CACHE)")
    ap.add_argument("--precision-manifest", default="precision_manifest.json",
                    metavar="PATH", dest="precision_manifest",
                    help="where the energy-pareto sweep writes the winning "
                         "mixed-precision deployment manifest (consumed by "
                         "serve.py --precision-manifest / "
                         "ServingConfig(precision_manifest=...))")
    args = ap.parse_args(argv)
    rows = run(small=args.small, precision_manifest=args.precision_manifest)
    if args.autotune:
        tuned_rows, entries = run_autotune(small=args.small)
        rows += tuned_rows
        autotune.save_cache(args.tune_cache, entries)
        print(f"wrote {args.tune_cache} ({len(entries)} tuned entries)",
              flush=True)
    if args.json_out:
        doc = rows_to_json(rows)
        with open(args.json_out, "w") as f:
            json.dump(doc, f, indent=1)
        print(f"wrote {args.json_out} ({len(doc['rows'])} rows)", flush=True)


if __name__ == "__main__":
    main()
