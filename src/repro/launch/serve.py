"""Serving launcher: continuous-batching decode over synthetic requests.

  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --requests 8 --slots 4 --max-new 16 [--cim bp]

  # paged-KV engine: block pool + chunked prefill through the unified step
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --prefill-chunk 8 --block-size 16 [--cim bp-prequant]

  # Pallas paged-attention kernel (block gather + online softmax in VMEM;
  # interpret mode on CPU) + static calibrated input-DAC scales
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --attn kernel [--cim bp --act-scale static]

  # consume a tuning cache from `kernel_bench --autotune`: dispatchers read
  # it via $REPRO_TUNE_CACHE; a tuned pool block size applies when
  # --block-size is not pinned explicitly
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --attn kernel --tune-cache tune_cache.json

  # prefix-sharing pool (default on for --paged): repeated prompts map onto
  # cached trie blocks; --n-samples forks N continuations copy-on-write off
  # one shared prefill; --watermark tunes the admission headroom
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --n-samples 4 [--no-prefix-sharing] [--watermark 0.1]

  # speculative decoding: the ngram drafter proposes K tokens per decode
  # lane, the target verifies them in ONE C=K+1 step; greedy streams are
  # bit-identical to plain decode. --temperature/--top-k/--sample-seed
  # switch the synthetic requests to seeded per-request sampling
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --drafter ngram --spec-k 4 [--temperature 0.8 --top-k 40] \
      [--trie-watermark 0.5]

  # per-site mixed analog precision: apply a precision_search deployment
  # manifest (from `kernel_bench --precision-manifest` or
  # analysis.precision_search.save_manifest) through CIMConfig
  # site_overrides; a missing/malformed/stale manifest warns and serves
  # uniform defaults
  PYTHONPATH=src python -m repro.launch.serve --arch internlm2-1.8b --smoke \
      --paged --cim bp --precision-manifest precision_manifest.json

  # telemetry export (runtime.telemetry / runtime.obs): Perfetto-loadable
  # Chrome trace (one track per slot + a scheduler track), Prometheus
  # text snapshot, JSONL event log; --arrival poisson replaces the
  # submit-all-at-once burst with seeded exponential inter-arrival gaps.
  # --arch defaults to internlm2-1.8b --smoke, so the minimal invocation is:
  PYTHONPATH=src python -m repro.launch.serve --paged \
      --trace-out trace.json --metrics-out metrics.prom \
      [--events-out events.jsonl] \
      [--arrival poisson --arrival-rate 8 --arrival-seed 0]

  REPRO_SERVE_DEVICES=4 PYTHONPATH=src python -m repro.launch.serve \
      --arch internlm2-1.8b --smoke --cim bp-noisy --mesh host [--paged]
      # EXECUTES (not just compiles) the shard_map-wrapped fused stochastic
      # kernels end-to-end on a small host mesh
"""
from __future__ import annotations

# Before ANY jax import: jax locks the device count at first init, so the
# optional multi-host-device serving mesh needs the flag set here.
import os
if os.environ.get("REPRO_SERVE_DEVICES"):
    os.environ["XLA_FLAGS"] = (
        "--xla_force_host_platform_device_count="
        f"{os.environ['REPRO_SERVE_DEVICES']} "
        + os.environ.get("XLA_FLAGS", ""))

import argparse
import contextlib
import time

import jax
import numpy as np

from repro.configs.registry import ARCHS, SMOKES
from repro.core.cim_matmul import CIMConfig
from repro.launch.compile_cache import enable_compile_cache
from repro.models import registry
from repro.parallel import sharding
from repro.runtime.server import Request, Server, ServingConfig
from repro.runtime.speculative import SamplingParams


def build_parser() -> argparse.ArgumentParser:
    """The serving flags (the namespace ServingConfig.from_flags maps)."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", choices=sorted(ARCHS),
                    default="internlm2-1.8b",
                    help="model architecture (default internlm2-1.8b so "
                         "the bare telemetry invocation works)")
    ap.add_argument("--smoke", action="store_true", default=True,
                    help="use the smoke-scale config (default on; "
                         "--full for the real geometry)")
    ap.add_argument("--full", dest="smoke", action="store_false",
                    help="use the full ARCHS config instead of the smoke "
                         "scale")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--slots", type=int, default=4)
    ap.add_argument("--max-new", type=int, default=16)
    ap.add_argument("--max-len", type=int, default=128)
    ap.add_argument("--paged", action="store_true",
                    help="paged-KV engine: block-pool cache + chunked "
                         "prefill through the unified jit'd step (decode is "
                         "the C=1 compilation); composes with --cim "
                         "bp-prequant (PackedCodes weights) and --mesh host")
    ap.add_argument("--block-size", type=int, default=None,
                    help="tokens per KV block (paged engine); default 16, "
                         "or the tuned layout when --tune-cache has one "
                         "for this window")
    ap.add_argument("--tune-cache", default=None, metavar="PATH",
                    help="kernel tuning cache from `kernel_bench "
                         "--autotune` — exported as $REPRO_TUNE_CACHE so "
                         "the attention/MVM dispatchers pick up tuned "
                         "configs, and consulted for a tuned paged-pool "
                         "block size when --block-size is not given")
    ap.add_argument("--num-blocks", type=int, default=None,
                    help="usable blocks in the pool (default: slot-cache "
                         "parity, slots × max-len / block-size)")
    ap.add_argument("--prefill-chunk", type=int, default=16,
                    help="prompt tokens per chunk through the unified step")
    ap.add_argument("--token-budget", type=int, default=None,
                    help="max new tokens per step across all lanes "
                         "(default: slots + prefill chunk)")
    ap.add_argument("--no-prefix-sharing", action="store_true",
                    help="disable the prefix trie (paged engine): every "
                         "request prefills its full prompt even when an "
                         "identical token prefix is already cached")
    ap.add_argument("--watermark", type=float, default=None,
                    help="free-block headroom fraction the paged admission "
                         "keeps in reserve (default 1/16; 0 disables — "
                         "admission then leans entirely on preemption)")
    ap.add_argument("--n-samples", type=int, default=1,
                    help="parallel samples per request (paged engine): one "
                         "shared prefill, N continuations forked "
                         "copy-on-write off the cached prefix")
    ap.add_argument("--drafter", default="off", metavar="SPEC",
                    help="speculative-decoding drafter "
                         "(runtime.speculative registry; paged engine): "
                         "off = plain decode, ngram = prompt-lookup "
                         "self-speculation, model:<name> = a small draft "
                         "model from configs.registry — the target "
                         "verifies all drafts in one C=spec-k+1 step; "
                         "token streams stay distribution-identical "
                         "(bit-identical under greedy)")
    ap.add_argument("--spec-k", type=int, default=None,
                    help="drafted tokens per decode lane per verify step "
                         "(default 4; only meaningful with --drafter)")
    ap.add_argument("--trie-watermark", type=float, default=None,
                    help="prefix-cache capacity fraction: when the trie "
                         "caches more than this fraction of the pool, an "
                         "LRU sweep (run every step, idle ones included) "
                         "drains it to half that — keeps long-lived "
                         "servers from pinning the pool in cold cache "
                         "(default: no sweep)")
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="sampling temperature for the synthetic requests "
                         "(0 = greedy; >0 samples the softmax with a "
                         "per-request seeded PRNG — bit-reproducible and "
                         "batch-composition invariant)")
    ap.add_argument("--top-k", type=int, default=0,
                    help="restrict sampling to the k highest logits "
                         "(0 = full vocab; needs --temperature > 0)")
    ap.add_argument("--sample-seed", type=int, default=0,
                    help="base sampling seed; request i uses seed + i")
    ap.add_argument("--attn", choices=("auto", "exact", "kernel"),
                    default="auto",
                    help="paged attention backend (kernels.paged_attention "
                         "registry): exact = window gather + one-pass "
                         "softmax (the [B,C,KH,G,W]-score reference), "
                         "kernel = Pallas flash decode/prefill over the "
                         "block tables (interpret mode on CPU), auto = "
                         "kernel unless REPRO_FORCE_JNP=1 pins exact")
    ap.add_argument("--act-scale", choices=("dynamic", "static"),
                    default="dynamic",
                    help="static = calibrate one fixed input-DAC grid "
                         "(analysis.calibrate amax sweep over a synthetic "
                         "batch) so each lane's CIM quantization is "
                         "independent of batch composition; needs --cim")
    ap.add_argument("--precision-manifest", default=None, metavar="PATH",
                    dest="precision_manifest",
                    help="mixed-precision deployment manifest "
                         "(analysis.precision_search JSON): installs "
                         "per-call-site (static grid, ADC levels, scheme, "
                         "per-channel) overrides into the CIM config; a "
                         "missing/malformed/stale file warns and serves "
                         "uniform defaults; needs --cim")
    ap.add_argument("--cim", choices=("off", "bp", "bp-noisy", "bp-prequant"),
                    default="off",
                    help="bp-noisy = NOISY converter chain with "
                         "noise_seed=0; backend=auto resolves to the fused "
                         "stochastic Pallas kernel (interpret mode on CPU) "
                         "— on a mesh (--mesh host) the engine wraps it in "
                         "shard_map, so sharded serving no longer falls "
                         "back to the jnp scan backend")
    ap.add_argument("--arrival", choices=("batch", "poisson"),
                    default="batch",
                    help="request arrival process: batch = submit all up "
                         "front (the historical behavior), poisson = "
                         "seeded exponential inter-arrival gaps paced in "
                         "real time — the seed of the ROADMAP traffic "
                         "harness, so the SLO numbers see bursty "
                         "admission instead of one burst")
    ap.add_argument("--arrival-rate", type=float, default=8.0,
                    help="mean requests/s for --arrival poisson")
    ap.add_argument("--arrival-seed", type=int, default=0,
                    help="numpy RNG seed for the arrival gaps "
                         "(deterministic schedule per seed)")
    ap.add_argument("--trace-out", default=None, metavar="PATH",
                    help="write a Chrome trace-event JSON of the request "
                         "lifecycle + scheduler steps — drag it into "
                         "https://ui.perfetto.dev")
    ap.add_argument("--metrics-out", default=None, metavar="PATH",
                    help="write a Prometheus text-exposition snapshot "
                         "(TTFT/ITL/accept-length/step-wall histograms, "
                         "event + kernel counters, pool gauges)")
    ap.add_argument("--events-out", default=None, metavar="PATH",
                    help="write the raw structured event log + step "
                         "snapshots as JSONL")
    ap.add_argument("--mesh", choices=("none", "host"), default="none",
                    help="host = shard serving over a data×model mesh of "
                         "the available host devices (set "
                         "REPRO_SERVE_DEVICES=N for N placeholder CPU "
                         "devices) — executes the mesh-sharded CIM engine "
                         "end-to-end")
    return ap


def install_mesh(args):
    """`--mesh host`: install a data×model mesh over the available devices
    as the process-wide serving mesh. Returns it (None for --mesh none)."""
    if args.mesh != "host":
        return None
    from repro.launch.mesh import make_host_smoke_mesh
    mesh, data, model = make_host_smoke_mesh()
    sharding.set_mesh(mesh)
    print(f"serving on host mesh data={data} model={model}")
    return mesh


def model_config(args):
    """The model config the flags select: smoke or full geometry, with the
    --cim preset applied."""
    cfg = (SMOKES if args.smoke else ARCHS)[args.arch]
    if args.cim == "bp-noisy":
        import dataclasses
        from repro.core.macro import SimLevel
        cim = CIMConfig(enabled=True, noise_seed=0)
        cfg = cfg.replace(cim=dataclasses.replace(
            cim, macro=dataclasses.replace(cim.macro,
                                           sim_level=SimLevel.NOISY)))
    elif args.cim != "off":
        cfg = cfg.replace(cim=CIMConfig(enabled=True))
    return cfg


def build_server(args, params=None) -> Server:
    """The Server the flags describe. `params` (from registry.init_params
    for this architecture) are initialised from seed 0 when not given; a
    caller serving several modes of one model passes them to share one
    copy. Flag combinations are validated by main()."""
    if args.tune_cache:
        os.environ["REPRO_TUNE_CACHE"] = args.tune_cache
    if args.block_size is None:
        args.block_size = 16
        if args.paged and args.tune_cache:
            from repro.kernels import autotune
            tuned = autotune.lookup("paged_attn",
                                    autotune.attn_family(args.max_len, 1),
                                    "kernel")
            if tuned and isinstance(tuned.get("block_size"), int) \
                    and args.max_len % tuned["block_size"] == 0:
                args.block_size = tuned["block_size"]
                print(f"tuned paged-pool block_size={args.block_size} "
                      f"(from {args.tune_cache})")
    cfg = model_config(args)
    if params is None:
        params = registry.init_params(jax.random.PRNGKey(0), cfg,
                                      max_seq=args.max_len)
    act_scale = act_zero_point = None
    if args.act_scale == "static":
        from repro.analysis.calibrate import calibrate_act_scale
        cal_rng = np.random.RandomState(7)
        cal_tokens = cal_rng.randint(0, cfg.vocab, size=(2, 16))
        cal = calibrate_act_scale(params, cal_tokens, cfg)
        act_scale = cal["scale"]
        act_zero_point = cal["zero_point"]
        print(f"calibrated static act_scale={act_scale:.6f} "
              f"zero_point={act_zero_point:.0f} "
              f"(max span {cal['span']:.4f} over {len(cal['spans'])} "
              f"matmul sites)")
    serving = ServingConfig.from_flags(args, act_scale=act_scale,
                                       act_zero_point=act_zero_point)
    return Server(params, cfg, serving)


def synthetic_requests(args, vocab: int) -> list[Request]:
    """The seeded synthetic workload: prompts of 4–16 random tokens."""
    rng = np.random.RandomState(0)
    reqs = []
    for i in range(args.requests):
        plen = int(rng.randint(4, 17))
        prompt = rng.randint(0, vocab, size=plen).tolist()
        reqs.append(Request(prompt=prompt, max_new_tokens=args.max_new,
                            n_samples=args.n_samples,
                            sampling=SamplingParams(
                                temperature=args.temperature,
                                top_k=args.top_k,
                                seed=args.sample_seed + i)))
    return reqs


def main():
    ap = build_parser()
    args = ap.parse_args()
    if args.precision_manifest and args.cim == "off":
        ap.error("--precision-manifest needs a --cim mode")
    if args.act_scale == "static" and args.cim == "off":
        ap.error("--act-scale static needs a --cim mode")
    enable_compile_cache()
    mesh = install_mesh(args)
    mesh_ctx = mesh if mesh is not None else contextlib.nullcontext()
    server = build_server(args)
    reqs = synthetic_requests(args, server.cfg.vocab)
    due = None
    if args.arrival == "poisson":
        arr_rng = np.random.RandomState(args.arrival_seed)
        gaps = arr_rng.exponential(1.0 / max(args.arrival_rate, 1e-9),
                                   size=len(reqs))
        due = np.cumsum(gaps)
        print(f"arrival=poisson rate={args.arrival_rate}/s "
              f"seed={args.arrival_seed} span={due[-1]:.2f}s")
    t0 = time.monotonic()
    with mesh_ctx:
        if due is None:
            for r in reqs:
                server.submit(r)
        else:
            # real-time pacing: submit each request at its arrival time;
            # step the server while waiting so in-flight lanes keep
            # decoding between arrivals (idle gaps just sleep)
            i = 0
            while i < len(reqs):
                now = time.monotonic() - t0
                if now >= due[i]:
                    server.submit(reqs[i])
                    i += 1
                elif any(r is not None for r in server.slot_req):
                    server.step()
                else:
                    time.sleep(min(float(due[i]) - now, 0.002))
        server.run_until_drained()
    dt = time.monotonic() - t0
    done = [s for r in reqs for s in (r, *r.samples)]
    total_new = sum(len(r.output) for r in done)
    for r in done:
        print(f"req{r.rid}: prompt_len={len(r.prompt)} -> {r.output}")
    print(f"{args.requests} requests x{args.n_samples}, {total_new} tokens, "
          f"{server.steps_run} decode steps, {dt:.2f}s "
          f"({total_new / max(dt, 1e-9):.1f} tok/s)")
    m = server.metrics.summary()
    kv = server.kv_cache_bytes()
    ttft = [r.ttft_s for r in done]
    lat = [r.latency_s for r in done]
    print(f"engine={'paged' if args.paged else 'slots'} "
          f"attn={args.attn if args.paged else '-'} "
          f"decode={m['decode_tok_s']:.1f} tok/s "
          f"prefill={m['prefill_tok_s']:.1f} tok/s "
          f"kv_bytes total={kv['total']} in_use={kv['in_use']}")
    print(f"ttft p50={np.median(ttft) * 1e3:.1f}ms "
          f"max={max(ttft) * 1e3:.1f}ms | latency "
          f"p50={np.median(lat) * 1e3:.1f}ms max={max(lat) * 1e3:.1f}ms")
    if args.paged:
        st = server.alloc.stats
        print(f"blocks: pool={st.num_blocks} peak={st.peak_in_use} "
              f"shared={st.shared} allocs={st.total_allocs} "
              f"frees={st.total_frees}")
        print(f"sharing: prefix_hit_tokens={m['prefix_hit_tokens']} "
              f"cow_forks={m['cow_forks']} "
              f"preemptions={m['preemptions']} "
              f"peak_active={m['peak_active']} "
              f"trie_sweep_freed={m['trie_sweep_freed']}")
        if args.drafter != "off":
            hist = ",".join(f"{a}:{n}" for a, n in m["accept_hist"].items())
            print(f"speculative: drafter={args.drafter} "
                  f"spec_k={server.serving.spec_k} "
                  f"verify_steps={m['spec_steps']} "
                  f"accept_rate={m['accept_rate']:.2f} "
                  f"mean_accept_len={m['mean_accept_len']:.2f} "
                  f"accept_hist=[{hist}]")

    tel = server.telemetry
    if tel.enabled and tel.ttft.n:
        print(f"slo: ttft p50={tel.ttft.percentile(50) * 1e3:.1f}ms "
              f"p99={tel.ttft.percentile(99) * 1e3:.1f}ms | "
              f"itl p50={tel.itl.percentile(50) * 1e3:.1f}ms "
              f"p99={tel.itl.percentile(99) * 1e3:.1f}ms | "
              f"step_wall p50={tel.step_wall.percentile(50) * 1e3:.1f}ms")
    if args.trace_out or args.metrics_out or args.events_out:
        import json
        from repro.runtime import obs
        if args.trace_out:
            doc = obs.chrome_trace(tel)
            with open(args.trace_out, "w") as f:
                json.dump(doc, f)
            print(f"wrote {args.trace_out} "
                  f"({len(doc['traceEvents'])} trace events) — load at "
                  f"https://ui.perfetto.dev")
        if args.metrics_out:
            with open(args.metrics_out, "w") as f:
                f.write(obs.prometheus_text(tel, server))
            print(f"wrote {args.metrics_out}")
        if args.events_out:
            n = obs.write_events_jsonl(tel, args.events_out)
            print(f"wrote {args.events_out} ({n} lines)")


if __name__ == "__main__":
    main()
