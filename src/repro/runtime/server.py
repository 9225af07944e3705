"""Continuous-batching serving loop: prefix-shared paged KV cache, chunked
prefill, watermark admission with preemption.

Construction goes through ONE config object::

    from repro.runtime.server import Request, Server, ServingConfig
    server = Server(params, cfg, ServingConfig(paged=True, n_slots=8,
                                               max_len=256, block_size=16))

`ServingConfig` consolidates what used to be an 11-keyword constructor
sprawl; validation lives in its `__post_init__`, and `from_flags(args)`
builds one from an argparse namespace (launch.serve). The PR-7 one-release
legacy keyword shim (`Server(params, cfg, n_slots=..., ...)`) is retired:
bare keyword construction now raises TypeError pointing here.

Two engines share one Server front end (submit / step / run_until_drained):

* **paged** (`ServingConfig(paged=True)`, the production path): a physical
  pool of fixed-size KV blocks shared by all slots, a REFCOUNTED
  `BlockAllocator` + `PrefixTrie` (runtime.paging), and per-slot block
  tables threaded through the model's attention reads/writes
  (models.transformer.paged_step). Resident KV bytes scale with the tokens
  actually cached, not n_slots × max_len. Prefill is CHUNKED through the
  same jit'd step as decode — decode is just the C=1 compilation of the
  unified step — and a token-budget scheduler caps new tokens per step
  (decode lanes first, then prompt chunks).

  PR-7 semantics on top of that engine:

  - **prefix sharing**: at admission the request's prompt is matched
    against the trie of previously cached full-block prefixes; the shared
    span maps the SAME physical blocks (zero prefill compute, zero new
    HBM), and only the tail is prefilled. Completed prefills register
    their full prompt blocks back into the trie. K/V content is a pure
    function of the absolute-position token prefix, so on the exact
    attention backend shared-block reuse is bit-identical to recompute.
  - **copy-on-write**: a lane about to write into a block some other
    holder also maps (refcount > 1 — a fork sibling's tail, a pending
    fork stash) first forks it: acquire a private block, device-copy the
    contents (models.transformer.cow_copy_block), remap the table. The
    step's fused write epilogue (kernels.paged_attention.fused_paged_write)
    computes its scatter targets from the REMAPPED table, so it lands in
    the private copy by construction.
  - **watermark admission + preemption**: instead of reserving a
    request's worst-case block count up front, admission only requires
    the prompt's unshared span plus a small watermark of headroom
    (`ServingConfig.watermark`, a fraction of the pool). When decode
    growth outruns the pool mid-flight, the scheduler first evicts
    least-recently-used trie entries, then PREEMPTS the newest-admitted
    lane: its full blocks are registered into the trie, its refs
    released, and the request re-queued at the head with an effective
    prompt of prompt + generated-so-far — resume re-admits through the
    trie, so only the sub-block tail recomputes. Greedy decode is
    deterministic, so a preempted request's final token stream is
    bit-identical to an unpreempted run (pinned by the preemption soak).
  - **parallel sampling**: `Request(n_samples=N)` decodes N greedy
    continuations off ONE prefill — clones share every prompt block and
    CoW-fork the partial tail on their first write. Clone requests are
    created at submit (`req.samples`) and installed, prefill-free, when
    the parent's prefill completes.

* **slot-based** (`paged=False`, the legacy engine, kept as the
  equivalence baseline): a monolithic [n_slots, max_len] cache; requests
  prefill individually (jit'd per prompt-length bucket) and are spliced
  into the batched cache; one shared `pos` clocks every slot. The paged
  soak tests pin the paged engine's outputs against this path and against
  one-request-at-a-time decode. NOTE the shared `pos` means slots admitted
  at different depths attend over zero-K/V gap positions (softmax
  dilution); the paged engine keeps true per-slot positions, so
  equivalence with this path is exact only on depth-aligned schedules —
  see tests/test_server_paged.py.

Sampling is per-request: `Request.sampling` carries a `SamplingParams`
(runtime.speculative) — greedy argmax by default (every bit-identity soak
pins that setting), or seeded temperature/top-k sampling whose draws are
keyed by (request seed, emission index) and therefore bit-reproducible and
batch-composition invariant. EOS/max-token retirement releases slots and
block refs. One deliberate semantic divergence: the legacy engine applies
neither the
max_new_tokens nor the eos_id check to the token emitted at prefill time;
the paged engine checks both and retires immediately, matching
one-request-at-a-time decode. Unservable requests (prompt ≥ max_len, or a
worst-case footprint larger than the whole pool) are rejected at submit()
so they can never poison the queue.

Attention backends (paged engine): `ServingConfig(attn=...)` selects the
paged step's attention path from the kernels.paged_attention registry —
"exact" (gather + one-pass softmax, the bit-identity anchor), "kernel"
(the Pallas flash kernel), or "auto" (kernel, unless REPRO_FORCE_JNP=1
pins exact). The bit-identity contracts (including preemption-resume and
prefix-shared admission) are pinned against attn="exact"; the kernel
backend agrees within float tolerance and has token-equality soaks of its
own.

The bit-identity contracts hold for FLOAT models (and any fixed schedule).
Under `cim.enabled` the engine's dynamic per-tensor act_scale couples every
lane's quantization grid to the whole batch's content, so CIM-mode outputs
depend on batch COMPOSITION — prefix sharing and preemption inherit that
caveat identically. The production fix is `ServingConfig(act_scale=...)`:
a static calibrated scale (analysis.calibrate) pins one fixed input-DAC
grid for every lane — pinned by tests/test_calibrate.py.

Speculative decoding (paged engine, PR 8): `ServingConfig(drafter=...)`
selects a drafter from the runtime.speculative registry ("off" — plain
decode; "ngram" — prompt-lookup self-speculation; "model:<name>" — a small
draft model from configs.registry). Each decode lane's drafter proposes up
to `spec_k` tokens from the lane's committed stream; the target verifies
all of them in ONE C=spec_k+1 `paged_step` (the all-positions-logits
compilation) and the longest agreeing prefix is accepted under exact
rejection sampling (runtime.speculative.verify_token) — token streams are
distribution-identical to plain decode and bit-identical under greedy.
The block pool makes rollback free: the verify step writes its K+1 K/V
entries into the lane's own blocks, and a rejection simply truncates the
committed `kv_len` (rejected positions are overwritten by the next step's
writes and are never readable — attention masks >= kv_len). Drafting,
clamping and accept/reject depend only on the lane's own state, so the
spec path preserves batch-composition invariance and preemption-resume
determinism.
"""
from __future__ import annotations

import dataclasses
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import ModelConfig
from repro.models import registry
from repro.parallel import sharding
from repro.runtime.paging import BlockAllocator, PrefixTrie, SlotTables
from repro.runtime.speculative import SamplingParams, make_drafter, \
    parse_drafter, sample_token, verify_token
from repro.runtime.telemetry import KERNEL_COUNTERS, Telemetry

# JAX's own compile events feed the process-wide compile counter from the
# moment serving is imported, so a deployment's set-up compiles count too
jax.monitoring.register_event_duration_secs_listener(
    KERNEL_COUNTERS.compile_event)


@dataclasses.dataclass(frozen=True)
class ServingConfig:
    """Everything the Server needs beyond (params, model cfg).

    Engine selection + capacity: `paged` picks the block-pool engine;
    `block_size` tokens per KV block; `num_blocks` usable blocks in the
    pool (default: slot-cache parity, n_slots × max_len / block_size —
    size it smaller to realize the paged memory win). Scheduling:
    `prefill_chunk` prompt tokens per chunk through the unified step;
    `token_budget` max new tokens per step across all lanes (default:
    n_slots + prefill_chunk). Sharing/preemption (paged only):
    `prefix_sharing` enables the trie + CoW machinery; `watermark` is the
    pool fraction admission keeps free as decode headroom (trading
    admission eagerness against preemption churn; 0 admits up to the last
    block). Weights: `prequant` re-encodes CIM-routed weights as stored
    codes (models.quantize), nibble-packed when `packed`. `attn` picks the
    paged attention backend; `act_scale` (+ optional `act_zero_point`) pins
    a static calibrated activation grid (analysis.calibrate) — needs
    cfg.cim.enabled. `precision_manifest` points at a mixed-precision
    deployment manifest (analysis.precision_search): per-call-site
    (grid, ADC levels, scheme, per-channel) overrides installed as
    cfg.cim.site_overrides, with the tune-cache fallback discipline — a
    missing/malformed/stale manifest warns and serves uniform defaults.
    Speculative decoding (paged only): `drafter` picks a proposer from the
    runtime.speculative registry ("off" / "ngram" / "model:<name>") and
    `spec_k` caps drafted tokens per lane per verify step. Trie capacity
    (paged + prefix_sharing): `trie_watermark` is a pool fraction — when
    the prefix cache exceeds it, an LRU sweep drains it to half that, so
    long-lived servers stop pinning the whole pool in cold cache between
    bursts (None disables; eviction then happens only under admission
    pressure). Observability: `telemetry` enables the per-request event
    trace / step snapshots / latency histograms (runtime.telemetry) —
    disable it only to measure its own overhead; the injectable-clock
    Server(telemetry=...) keyword overrides this flag entirely.
    """
    n_slots: int = 4
    max_len: int = 128
    prequant: bool = False
    packed: bool = True
    paged: bool = False
    block_size: int = 16
    num_blocks: Optional[int] = None
    prefill_chunk: int = 16
    token_budget: Optional[int] = None
    attn: str = "auto"
    act_scale: Optional[float] = None
    act_zero_point: Optional[float] = None
    precision_manifest: Optional[str] = None
    prefix_sharing: bool = True
    watermark: float = 1 / 16
    drafter: str = "off"
    spec_k: int = 4
    trie_watermark: Optional[float] = None
    telemetry: bool = True

    def __post_init__(self):
        if self.n_slots < 1:
            raise ValueError("n_slots must be >= 1")
        if self.max_len < 2:
            raise ValueError("max_len must be >= 2")
        if self.prefill_chunk < 1:
            raise ValueError("prefill_chunk must be >= 1")
        if self.token_budget is not None and self.token_budget < 1:
            raise ValueError("token_budget must be >= 1 (a 0 budget "
                             "would step forever without progress)")
        if self.paged:
            if self.block_size < 1:
                raise ValueError("block_size must be >= 1")
            if self.max_len % self.block_size:
                raise ValueError("max_len must be a multiple of block_size")
            if self.num_blocks is not None and self.num_blocks < 1:
                raise ValueError("num_blocks must be >= 1")
        if not 0.0 <= self.watermark < 1.0:
            raise ValueError("watermark is a pool fraction in [0, 1)")
        if self.spec_k < 1:
            raise ValueError("spec_k must be >= 1 (tokens drafted per "
                             "verify step)")
        if self.act_zero_point is not None and self.act_scale is None:
            raise ValueError("act_zero_point positions a static grid — it "
                             "needs act_scale (the grid's step) set too")
        from repro.kernels.paged_attention import choose_attn_backend
        choose_attn_backend(self.attn)   # validate the name up front
        name, _ = parse_drafter(self.drafter)   # validate like attn
        if name != "off" and not self.paged:
            raise ValueError("speculative decoding (drafter != 'off') "
                             "needs the paged engine (paged=True)")
        if self.trie_watermark is not None:
            if not 0.0 < self.trie_watermark <= 1.0:
                raise ValueError("trie_watermark is a pool fraction in "
                                 "(0, 1]")
            if not (self.paged and self.prefix_sharing):
                raise ValueError("trie_watermark needs the paged engine "
                                 "with prefix_sharing enabled")

    @classmethod
    def from_flags(cls, args, **overrides) -> "ServingConfig":
        """Build from an argparse namespace (launch.serve's flag names);
        missing attributes keep their defaults, `overrides` win last (the
        launcher passes the calibrated act_scale value this way)."""
        kw = {}
        pairs = [("n_slots", "slots"), ("max_len", "max_len"),
                 ("paged", "paged"), ("block_size", "block_size"),
                 ("num_blocks", "num_blocks"),
                 ("prefill_chunk", "prefill_chunk"),
                 ("token_budget", "token_budget"), ("attn", "attn"),
                 ("watermark", "watermark"), ("drafter", "drafter"),
                 ("spec_k", "spec_k"),
                 ("trie_watermark", "trie_watermark"),
                 ("precision_manifest", "precision_manifest")]
        for field, flag in pairs:
            v = getattr(args, flag, None)
            if v is not None:
                kw[field] = v
        if getattr(args, "no_prefix_sharing", False):
            kw["prefix_sharing"] = False
        if getattr(args, "cim", None) == "bp-prequant":
            kw["prequant"] = True
        kw.update(overrides)
        return cls(**kw)


@dataclasses.dataclass
class Request:
    prompt: list[int]
    max_new_tokens: int = 16
    eos_id: Optional[int] = None
    n_samples: int = 1       # paged engine: continuations off one prefill
    # per-request sampling policy (runtime.speculative): greedy default;
    # temperature/top-k draws are keyed by (sampling.seed, emission index)
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams)
    # filled by the server:
    rid: int = -1
    output: list[int] = dataclasses.field(default_factory=list)
    done: bool = False
    samples: list["Request"] = dataclasses.field(default_factory=list)
    # per-request latency metrics (monotonic timestamps)
    t_submit: float = 0.0
    t_first: float = 0.0     # first token emitted (prefill complete)
    t_done: float = 0.0

    @property
    def ttft_s(self) -> float:
        return max(self.t_first - self.t_submit, 0.0)

    @property
    def latency_s(self) -> float:
        return max(self.t_done - self.t_submit, 0.0)


@dataclasses.dataclass
class ServerMetrics:
    steps: int = 0
    decode_tokens: int = 0    # tokens emitted by decode lanes
    prefill_tokens: int = 0   # prompt tokens actually prefilled
    stalled_prefills: int = 0  # prefill lanes given 0 budget in a step
    stalled_decodes: int = 0   # decode lanes dropped by the token budget
    preemptions: int = 0       # lanes evicted under pool pressure
    prefix_hit_tokens: int = 0  # prefill tokens skipped via shared blocks
    cow_forks: int = 0         # shared blocks privatized before a write
    spec_steps: int = 0        # speculative verify steps run
    draft_tokens: int = 0      # tokens proposed by the drafter
    draft_accepted: int = 0    # proposed tokens accepted by verification
    # accept-length histogram: {accepted drafts per verify step: count}
    accept_hist: dict = dataclasses.field(default_factory=dict)
    trie_sweep_freed: int = 0  # blocks freed by trie watermark sweeps
    peak_active: int = 0       # max concurrently active lanes in a step
    peak_decode_lanes: int = 0  # max lanes past prefill in one step — the
    #                             pool-capacity-limited concurrency (admitted
    #                             lanes can transiently exceed what the pool
    #                             sustains; decode lanes cannot)
    wall_s: float = 0.0       # time inside step() + admission-time prefill
    # pool composition sampled at the end of each paged step (and at
    # construction): blocks_total/free/shared/cached_cold/private +
    # trie_entries — see Server._pool_stats for the split semantics
    pool: dict = dataclasses.field(default_factory=dict)

    def summary(self) -> dict:
        w = max(self.wall_s, 1e-9)
        return {"steps": self.steps,
                "decode_tokens": self.decode_tokens,
                "prefill_tokens": self.prefill_tokens,
                "decode_tok_s": self.decode_tokens / w,
                "prefill_tok_s": self.prefill_tokens / w,
                "stalled_prefills": self.stalled_prefills,
                "stalled_decodes": self.stalled_decodes,
                "preemptions": self.preemptions,
                "prefix_hit_tokens": self.prefix_hit_tokens,
                "cow_forks": self.cow_forks,
                "spec_steps": self.spec_steps,
                "draft_tokens": self.draft_tokens,
                "draft_accepted": self.draft_accepted,
                "accept_rate": self.draft_accepted / self.draft_tokens
                if self.draft_tokens else 0.0,
                # mean emissions per verify step (accepted drafts + the
                # correction/bonus token) — tokens-per-target-call, the
                # speculative speedup axis
                "mean_accept_len": 1.0 + self.draft_accepted
                / self.spec_steps if self.spec_steps else 0.0,
                "accept_hist": dict(sorted(self.accept_hist.items())),
                "trie_sweep_freed": self.trie_sweep_freed,
                "peak_active": self.peak_active,
                "peak_decode_lanes": self.peak_decode_lanes,
                "wall_s": self.wall_s}

    def to_dict(self) -> dict:
        """summary() plus the KV-pool composition (shared / private /
        cached-cold block split and prefix-trie entry count) — the
        post-run view the preemption soaks and exporters assert on."""
        return {**self.summary(), **self.pool}


class Server:
    def __init__(self, params, cfg: ModelConfig,
                 serving: ServingConfig | None = None, *,
                 telemetry: Telemetry | None = None, **legacy):
        if legacy:
            # the PR-7 one-release DeprecationWarning shim is retired:
            # keyword construction fails loudly with the migration target
            raise TypeError(
                f"Server() no longer accepts bare keyword arguments "
                f"{sorted(legacy)}; construct a ServingConfig and pass "
                "Server(params, cfg, ServingConfig(...))")
        if serving is None:
            serving = ServingConfig()
        self.serving = serving
        # the telemetry sink is injectable (tests pass a fake clock); a
        # caller-provided instance wins over the ServingConfig.telemetry
        # on/off flag
        self.telemetry = telemetry if telemetry is not None \
            else Telemetry(enabled=serving.telemetry)
        # spans land in a device trace as profiler annotations
        self.telemetry.annotation = jax.profiler.TraceAnnotation
        with self.telemetry.span("build"):
            self._build(params, cfg, serving)

    def _build(self, params, cfg: ModelConfig, serving: ServingConfig):
        cfg = cfg.replace(attn_backend=serving.attn)
        if serving.act_scale is not None:
            assert cfg.cim.enabled, "static act_scale needs cim.enabled"
            cfg = cfg.replace(cim=dataclasses.replace(
                cfg.cim, act=dataclasses.replace(
                    cfg.cim.act, static_scale=float(serving.act_scale),
                    static_zero_point=float(serving.act_zero_point or 0.0))))
        if serving.precision_manifest is not None:
            assert cfg.cim.enabled, "precision manifest needs cim.enabled"
            from repro.analysis.precision_search import apply_manifest, \
                load_manifest
            manifest = load_manifest(serving.precision_manifest,
                                     arch=cfg.arch)
            # None (missing/malformed/stale) falls through unchanged: the
            # server comes up on uniform defaults, mirroring the tune cache
            cfg = cfg.replace(cim=apply_manifest(cfg.cim, manifest))
        if serving.prequant:
            assert cfg.cim.enabled, "prequant serving needs cim.enabled"
            from repro.models.quantize import quantize_params
            with self.telemetry.span("quantize"):
                params = jax.block_until_ready(
                    quantize_params(params, cfg, packed=serving.packed))
        if sharding.get_mesh() is not None:
            # freshly initialised parameters all sit on the first device;
            # lay them out by the PARAM_RULES so each device holds its shard
            params = jax.device_put(params, sharding.tree_shardings(params))
        self.params = params
        self.cfg = cfg
        self.n_slots = serving.n_slots
        self.max_len = serving.max_len
        self.mod = registry.get_module(cfg)
        self.paged = serving.paged
        self.slot_req: list[Optional[Request]] = [None] * self.n_slots
        self.queue: list[Request] = []
        self._next_rid = 0
        self.steps_run = 0
        self.metrics = ServerMetrics()

        if self.paged:
            if not (hasattr(self.mod, "paged_step")
                    and self.mod.supports_paged(cfg)):
                raise NotImplementedError(
                    f"paged serving not supported for arch {cfg.arch!r}")
            self.block_size = serving.block_size
            max_blocks = self.max_len // self.block_size
            num_blocks = serving.num_blocks
            if num_blocks is None:
                num_blocks = self.n_slots * max_blocks
            self.alloc = BlockAllocator(num_blocks)
            self.tables = SlotTables(self.n_slots, max_blocks,
                                     self.block_size)
            self.trie = PrefixTrie(self.block_size) \
                if serving.prefix_sharing else None
            self.prefill_chunk = serving.prefill_chunk
            self.token_budget = serving.token_budget \
                if serving.token_budget is not None \
                else self.n_slots + self.prefill_chunk
            self._watermark = max(1, round(num_blocks * serving.watermark)) \
                if serving.watermark > 0 else 0
            # pool holds num_blocks usable blocks + the trash block (id 0)
            def init_pool():
                return self.mod.init_paged_cache(cfg, num_blocks + 1,
                                                 self.block_size)
            pool_shardings = None
            if sharding.get_mesh() is not None:
                # pools [L, NB, KH, bs, dh]: KV heads over "model", the
                # layout the paged-attention mesh dispatch reads
                pool_shardings = jax.tree.map(
                    lambda a: sharding.sharding_for(
                        a.shape, (None, None, "tp", None, None)),
                    jax.eval_shape(init_pool))
            with self.telemetry.span("pool"):
                self.cache = jax.block_until_ready(
                    jax.jit(init_pool, out_shardings=pool_shardings)())

            # named functions, so the compiled modules are named for them
            def paged_step(p, t, c, tb, ln, vd):
                return self.mod.paged_step(p, t, c, tb, ln, vd, cfg)

            def paged_step_all(p, t, c, tb, ln, vd):
                return self.mod.paged_step(p, t, c, tb, ln, vd, cfg,
                                           all_logits=True)

            self._pstep = jax.jit(paged_step)
            # speculative decoding: the drafter instance (None = off) and
            # the all-positions-logits compilation its verify steps use
            # (one C=spec_k+1 call scores every drafted token at once)
            self.spec_k = serving.spec_k
            self.drafter = make_drafter(serving.drafter, cfg, self.max_len)
            self._pstep_all = jax.jit(paged_step_all)
            # trie capacity watermarks (block counts; 0 = sweep disabled)
            self._trie_hi = self._trie_lo = 0
            if self.trie is not None and serving.trie_watermark is not None:
                self._trie_hi = max(1, int(num_blocks
                                           * serving.trie_watermark))
                self._trie_lo = self._trie_hi // 2
            # CoW block copy: one compilation (src/dst are traced scalars),
            # donated pools so the fork is an in-place device copy
            self._cow = jax.jit(
                lambda c, src, dst: self.mod.cow_copy_block(c, src, dst),
                donate_argnums=0)
            self._pf_done = np.zeros(self.n_slots, np.int64)
            self._pf_src: list[Optional[list[int]]] = [None] * self.n_slots
            self._slot_seq = np.zeros(self.n_slots, np.int64)
            self._adm_seq = 0
            self._fork_children: dict[int, list[Request]] = {}
            self._fork_ready: dict[int, dict] = {}
            self._rr = 0   # round-robin offset for budget-capped decode
            self._preempted_rids: set[int] = set()
            self.metrics.pool = self._pool_stats()
        else:
            self.slot_len = np.zeros(self.n_slots, np.int32)
            self.cache = jax.jit(
                lambda: self.mod.init_cache(cfg, self.n_slots,
                                            self.max_len))()
            self._decode = jax.jit(
                lambda p, t, c: self.mod.decode_step(p, t, c, cfg))
            self._prefill = jax.jit(
                lambda p, b: self.mod.prefill(p, b, cfg,
                                              max_len=self.max_len),
                static_argnames=())

    # -- request lifecycle ---------------------------------------------------
    def submit(self, req: Request) -> int:
        with self.telemetry.span("submit"):
            return self._submit(req)

    def _submit(self, req: Request) -> int:
        # reject unservable requests BEFORE queueing: a poison request at
        # the queue head would otherwise either stall admission forever
        # (a footprint larger than the whole pool — run_until_drained
        # would spin) or crash mid-serve and strand the in-flight
        # requests.
        if not req.prompt:
            raise ValueError("empty prompt")
        if req.n_samples < 1:
            raise ValueError("n_samples must be >= 1")
        if not isinstance(req.sampling, SamplingParams):
            raise ValueError("Request.sampling must be a SamplingParams "
                             f"(runtime.speculative), got "
                             f"{type(req.sampling).__name__}")
        if self.paged:
            if len(req.prompt) >= self.max_len - 1:
                raise ValueError(
                    f"prompt of {len(req.prompt)} tokens exceeds "
                    f"max_len={self.max_len}")
            need = self._blocks_worst_case(req)
            if req.n_samples > 1:
                # a sibling's CoW fork keeps the shared original alive in
                # the stash while the private copy grows
                need += 1
            if need > self.alloc.stats.num_blocks:
                raise ValueError(
                    f"request needs {need} KV blocks worst-case but the "
                    f"pool only has {self.alloc.stats.num_blocks}")
        elif req.n_samples > 1:
            raise ValueError("parallel sampling (n_samples > 1) needs the "
                             "paged engine")
        req.rid = self._next_rid
        req.t_submit = self.telemetry.now()
        self._next_rid += 1
        self.telemetry.submit(req.rid, req.t_submit, len(req.prompt),
                              req.n_samples)
        if self.paged and req.n_samples > 1:
            kids = []
            for i in range(req.n_samples - 1):
                # clones get distinct PRNG streams (seed + sibling index)
                # so sampled parallel continuations actually diverge;
                # greedy clones stay bit-identical to the parent
                c = Request(prompt=list(req.prompt),
                            max_new_tokens=req.max_new_tokens,
                            eos_id=req.eos_id,
                            sampling=dataclasses.replace(
                                req.sampling, seed=req.sampling.seed + i + 1))
                c.rid = self._next_rid
                self._next_rid += 1
                c.t_submit = req.t_submit
                self.telemetry.submit(c.rid, c.t_submit, len(c.prompt), 1)
                kids.append(c)
            req.samples = list(kids)
            self._fork_children[req.rid] = kids
        self.queue.append(req)
        # admission work (incl. the legacy engine's per-request prefill)
        # counts toward wall_s so both engines' tok/s share one clock
        t0 = self.telemetry.now()
        self._admit()
        self.metrics.wall_s += self.telemetry.now() - t0
        return req.rid

    def _admit(self):
        if self.paged:
            self._admit_paged()
            return
        for slot in range(self.n_slots):
            if self.slot_req[slot] is None and self.queue:
                req = self.queue.pop(0)
                self._prefill_into(slot, req)

    def _prefill_into(self, slot: int, req: Request):
        tokens = jnp.asarray([req.prompt], jnp.int32)
        batch = {"tokens": tokens}
        logits, rcache = self._prefill(self.params, batch)
        first = sample_token(np.asarray(logits[0]), req.sampling,
                             len(req.output))
        req.output.append(first)
        req.t_first = self.telemetry.now()
        self.telemetry.admit(req.rid, slot, req.t_first,
                             prefix_hit_blocks=0,
                             prefill_tokens=len(req.prompt))
        self.telemetry.prefill_chunk(req.rid, slot, req.t_first,
                                     len(req.prompt), len(req.prompt),
                                     len(req.prompt))
        self.telemetry.first_token(req.rid, slot, req.t_first,
                                   req.t_submit)
        self.metrics.prefill_tokens += len(req.prompt)
        self.slot_req[slot] = req
        self.slot_len[slot] = len(req.prompt)
        self.cache = _splice(self.cache, rcache, slot)

    # -- decode loop ----------------------------------------------------------
    def step(self):
        """One serving step; retires finished requests and re-admits."""
        t0 = self.telemetry.now()
        if self.paged:
            self._step_paged()
        else:
            self._step_slots()
        self.metrics.wall_s += self.telemetry.now() - t0

    def _sweep_trie(self):
        # trie capacity policy: the watermark sweep runs every step —
        # idle ones included — so a long-lived server's cold prefix cache
        # drains between bursts
        if self._trie_hi and self.trie is not None:
            self.metrics.trie_sweep_freed += self.trie.sweep(
                self.alloc, self._trie_hi, self._trie_lo)

    def _step_slots(self):
        """Legacy engine: one decode step for all slots."""
        active = [s for s in range(self.n_slots) if self.slot_req[s]]
        if not active:
            return
        toks = np.zeros((self.n_slots, 1), np.int32)
        for s in active:
            toks[s, 0] = self.slot_req[s].output[-1]
        # align the shared cache position to the deepest slot
        pos = int(max(self.slot_len[s] + len(self.slot_req[s].output) - 1
                      for s in active))
        self.cache["pos"] = jnp.asarray(pos, jnp.int32)
        logits, self.cache = self._decode(self.params, jnp.asarray(toks),
                                          self.cache)
        rows = np.asarray(logits)
        now = self.telemetry.now()
        for s in active:
            req = self.slot_req[s]
            nxt = sample_token(rows[s], req.sampling, len(req.output))
            req.output.append(nxt)
            self.metrics.decode_tokens += 1
            self.telemetry.emission(req.rid, s, now)
            exhausted = len(req.output) >= req.max_new_tokens
            hit_eos = req.eos_id is not None and nxt == req.eos_id
            if exhausted or hit_eos or pos + 1 >= self.max_len - 1:
                req.done = True
                req.t_done = now
                self.telemetry.retire(req.rid, s, now,
                                      tokens=len(req.output),
                                      latency_s=req.latency_s)
                self.slot_req[s] = None
                self.slot_len[s] = 0
        self.steps_run += 1
        self.metrics.steps += 1
        self._admit()

    # -- paged engine ---------------------------------------------------------
    def _blocks_worst_case(self, req: Request) -> int:
        """Every block the request may ever hold at once (prompt +
        generated; the final sampled token is never written). Used only
        for the submit-time can-this-EVER-fit rejection — admission itself
        is watermark-based."""
        need = min(len(req.prompt) + req.max_new_tokens, self.max_len)
        return self.tables.blocks_for(need)

    def _available(self) -> int:
        """Blocks admission can count on: free now + trie-evictable."""
        n = self.alloc.stats.free
        if self.trie is not None:
            n += self.trie.evictable(self.alloc)
        return n

    def _admit_paged(self):
        while self.queue:
            try:
                slot = self.slot_req.index(None)
            except ValueError:
                return
            req = self.queue[0]
            if req.rid in self._fork_ready:
                # fork clones map already-referenced blocks: zero new HBM,
                # no prefill, no watermark interaction
                self.queue.pop(0)
                self._install_fork(slot, req)
                continue
            # effective prompt: original prompt + anything generated before
            # a preemption (resume is a prefill of the longer prompt; the
            # trie turns most of it into a free match)
            eff = req.prompt + req.output
            matched = self.trie.match(eff[:-1]) if self.trie is not None \
                else []
            need = self.tables.blocks_for(len(eff)) - len(matched)
            headroom = self._watermark if any(
                r is not None for r in self.slot_req) else 0
            if self._available() < need + headroom:
                return  # head-of-line waits; active lanes keep draining
            self.queue.pop(0)
            self.slot_req[slot] = req
            self._slot_seq[slot] = self._adm_seq
            self._adm_seq += 1
            if matched:
                self.alloc.incref(matched)
                self.tables.assign(slot, matched,
                                   len(matched) * self.block_size)
                self.metrics.prefix_hit_tokens += \
                    len(matched) * self.block_size
            self._pf_src[slot] = eff
            self._pf_done[slot] = len(matched) * self.block_size
            # a previously-preempted rid re-admitting is a resume (even if
            # it was preempted mid-prefill, before emitting anything)
            resume = req.rid in self._preempted_rids
            self._preempted_rids.discard(req.rid)
            self.telemetry.admit(
                req.rid, slot, self.telemetry.now(),
                prefix_hit_blocks=len(matched),
                prefill_tokens=len(eff) - len(matched) * self.block_size,
                resume=resume)

    def _install_fork(self, slot: int, req: Request):
        info = self._fork_ready.pop(req.rid)
        self.slot_req[slot] = req
        self._slot_seq[slot] = self._adm_seq
        self._adm_seq += 1
        self.tables.assign(slot, info["blocks"], info["lens"])
        self._pf_src[slot] = []          # nothing to prefill: pure decode
        self._pf_done[slot] = 0
        req.output = list(info["output"])
        now = self.telemetry.now()
        self.telemetry.admit(req.rid, slot, now,
                             prefix_hit_blocks=len(info["blocks"]),
                             prefill_tokens=0, fork=True)
        if not req.t_first:
            req.t_first = now
            self.telemetry.first_token(req.rid, slot, now, req.t_submit)
        self.metrics.prefix_hit_tokens += info["lens"]
        if (len(req.output) >= req.max_new_tokens
                or (req.eos_id is not None
                    and req.output[-1] == req.eos_id)):
            self._retire_paged(slot, now)

    def _schedule(self, active):
        """Pick this step's lanes under the token budget: decode first
        (latency-critical, 1 token each), then prompt chunks. Returns
        (decode_lanes, dropped_decodes, takes, starved_prefills)."""
        prefilling = [s for s in active
                      if self._pf_done[s] < len(self._pf_src[s])]
        budget = self.token_budget
        cands = [s for s in active if s not in prefilling]
        if cands:
            rot = self._rr % len(cands)
            cands = cands[rot:] + cands[:rot]
        decode_lanes = cands[:budget]
        dropped = len(cands) - len(decode_lanes)
        budget -= len(decode_lanes)
        takes: dict[int, int] = {}
        starved = 0
        for s in prefilling:
            take = min(len(self._pf_src[s]) - int(self._pf_done[s]),
                       self.prefill_chunk, budget)
            if take <= 0:
                starved += 1
                continue
            takes[s] = take
            budget -= take
        return decode_lanes, dropped, takes, starved

    def _write_plan(self, valid_map: dict[int, int]):
        """Blocks this step must acquire: table growth for new positions,
        plus one private copy per shared block about to be written (CoW).
        Returns (total_new_blocks, [(slot, logical_idx, shared_block)])."""
        bs = self.block_size
        need, copies = 0, []
        for s, v in valid_map.items():
            if not v:
                continue
            lens = int(self.tables.lens[s])
            new_len = lens + v
            need += max(0, self.tables.blocks_for(new_len)
                        - int(self.tables.n_alloc[s]))
            # writes land in logical blocks [lens//bs, (new_len-1)//bs];
            # only already-held blocks can be shared (growth is private)
            for j in range(lens // bs,
                           min((new_len - 1) // bs + 1,
                               int(self.tables.n_alloc[s]))):
                b = int(self.tables.tables[s, j])
                if self.alloc.refcount(b) > 1:
                    copies.append((s, j, b))
                    need += 1
        return need, copies

    def _step_paged(self):
        tel = self.telemetry
        if not any(r is not None for r in self.slot_req):
            with tel.span("admit"):
                self._sweep_trie()
            return
        t_begin = tel.now()
        with tel.span("plan"):
            # plan the step; preempt the newest-admitted lane while the pool
            # cannot back every write (evictable trie entries count as room —
            # they are freed below, before acquiring)
            while True:
                active = [s for s in range(self.n_slots) if self.slot_req[s]]
                if not active:
                    return
                decode_lanes, dropped, takes, starved = self._schedule(active)
                spec = self._plan_spec(decode_lanes)
                valid_map = {s: 1 + len(spec.get(s, ())) for s in decode_lanes}
                valid_map.update(takes)
                need, copies = self._write_plan(valid_map)
                if need <= self._available() or len(active) == 1:
                    break
                # newest admission loses: FIFO fairness, and its trie overlap
                # makes its resume the cheapest recompute
                victim = max(active, key=lambda s: int(self._slot_seq[s]))
                self._preempt(victim)
            self._rr += 1
            self.metrics.stalled_decodes += dropped
            self.metrics.stalled_prefills += starved
            self.metrics.peak_active = max(self.metrics.peak_active,
                                           len(active))
            self.metrics.peak_decode_lanes = max(
                self.metrics.peak_decode_lanes, len(decode_lanes))
            # make room, then privatize shared write targets, then back the
            # new positions. With one active lane the submit-time worst-case
            # check guarantees this always fits (see _blocks_worst_case).
            shortfall = need - self.alloc.stats.free
            if shortfall > 0 and self.trie is not None:
                self.trie.evict(shortfall, self.alloc)
            if not self.alloc.can_acquire(need):
                raise RuntimeError(
                    f"pool cannot back this step: need {need} blocks, "
                    f"free {self.alloc.stats.free} — scheduler invariant "
                    "violated")
            for s, j, b in copies:
                [nb] = self.alloc.acquire(1)
                self.cache = self._cow(self.cache, jnp.asarray(b, jnp.int32),
                                       jnp.asarray(nb, jnp.int32))
                self.tables.replace(s, j, nb, self.alloc)
                self.metrics.cow_forks += 1
                self.telemetry.cow_fork(self.slot_req[s].rid, s,
                                        self.telemetry.now(), b, nb)
            for s, v in valid_map.items():
                if v:
                    self.tables.grow(s, int(self.tables.lens[s]) + v,
                                     self.alloc)
            # chunk width: steps whose prefill lanes are all budget-starved
            # run the cheap C=1 decode compilation; spec verify lanes always
            # stamp C=spec_k+1 (per-lane clamps shrink `valid`, never the
            # traced shape, so the compiled-shape set stays bounded)
            c = 1
            if takes:
                c = self.prefill_chunk
            if spec:
                c = max(c, self.spec_k + 1)
            toks = np.zeros((self.n_slots, c), np.int32)
            valid = np.zeros(self.n_slots, np.int32)
            for s in decode_lanes:
                toks[s, 0] = self.slot_req[s].output[-1]
                drafts = spec.get(s, ())
                toks[s, 1:1 + len(drafts)] = drafts
                valid[s] = 1 + len(drafts)
            for s, take in takes.items():
                done = int(self._pf_done[s])
                src = self._pf_src[s]
                toks[s, :take] = src[done:done + take]
                valid[s] = take
            toks_d, valid_d = jnp.asarray(toks), jnp.asarray(valid)
            tables_d = jnp.asarray(self.tables.tables)
            lens_d = jnp.asarray(self.tables.lens)
        # verify steps need the logits at EVERY chunk position (one row
        # per drafted token plus the bonus); everything else keeps the
        # last-position compilation
        pstep = self._pstep_all if spec else self._pstep
        with tel.span("device", c=c):
            logits, self.cache = pstep(self.params, toks_d, self.cache,
                                       tables_d, lens_d, valid_d)
            jax.block_until_ready(logits)
        with tel.span("fetch"):
            rows = np.asarray(logits)           # [B, V] or [B, C, V]
        with tel.span("sample"):
            now = self.telemetry.now()
            dec_lanes: list = []                # plain-decode emissions this
            retires: list = []                  # step, batched into ONE ring
            for s in active:                    # event after the lane loop
                if not valid[s]:
                    continue
                req = self.slot_req[s]
                if s in takes:
                    self.tables.lens[s] += int(valid[s])
                    self._pf_done[s] += int(valid[s])
                    self.metrics.prefill_tokens += int(valid[s])
                    self.telemetry.prefill_chunk(
                        req.rid, s, now, int(valid[s]),
                        int(self._pf_done[s]), len(self._pf_src[s]))
                    if self._pf_done[s] == len(self._pf_src[s]):
                        row = rows[s, int(valid[s]) - 1] if rows.ndim == 3 \
                            else rows[s]
                        # emission index = len(output): 0 for a fresh prompt,
                        # the resume index after preemption — either way the
                        # same (seed, index) PRNG key plain decode would use
                        req.output.append(
                            sample_token(row, req.sampling, len(req.output)))
                        if not req.t_first:
                            req.t_first = now
                            self.telemetry.first_token(req.rid, s, now,
                                                       req.t_submit)
                        else:
                            # resume completion re-emits a token: the ITL
                            # sample spans the preemption gap on purpose
                            self.telemetry.emission(req.rid, s, now)
                        self._register_prefix(s)
                        self._stash_forks(s)
                        # one-at-a-time semantics: exhaustion AND EOS apply
                        # to the prefill-emitted token too (the legacy
                        # engine checks neither here — see the module
                        # docstring)
                        if (len(req.output) >= req.max_new_tokens
                                or (req.eos_id is not None
                                    and req.output[-1] == req.eos_id)):
                            self._retire_paged(s, now)
                    continue
                if s in spec:
                    self._apply_verify(s, rows[s], spec[s], now)
                    continue
                self.tables.lens[s] += 1
                row = rows[s, 0] if rows.ndim == 3 else rows[s]
                nxt = sample_token(row, req.sampling, len(req.output))
                req.output.append(nxt)
                self.metrics.decode_tokens += 1
                dec_lanes.append((req.rid, s))
                exhausted = len(req.output) >= req.max_new_tokens
                hit_eos = req.eos_id is not None and nxt == req.eos_id
                full = int(self.tables.lens[s]) + 1 >= self.max_len - 1
                if exhausted or hit_eos or full:
                    retires.append(s)
            # one batched decode event for the whole step's plain emissions
            # (per-lane ITL samples are still recorded inside), THEN the
            # retires so each rid's ring ends with its retire event
            self.telemetry.decode_step(dec_lanes, now)
            for s in retires:
                self._retire_paged(s, now)
            self.steps_run += 1
            self.metrics.steps += 1
        # sample pool composition + scheduler state once per step; the
        # pool dict also lands on ServerMetrics so to_dict() reflects the
        # post-run split even with telemetry disabled
        with tel.span("admit"):
            pool = self._pool_stats()
            self.metrics.pool = pool
            self._admit()
            self._sweep_trie()
        t_end = tel.now()
        # after the admit span, whose duration it stamps; positional on
        # purpose (field order == StepSnapshot): the 16-kwarg binding was
        # the most expensive part of the per-step telemetry call and both
        # legs pay it before the enabled check
        tel.step_snapshot(
            self.steps_run, t_end, t_end - t_begin,             # step/t/wall
            len(active), len(decode_lanes), len(takes),         # lane mix
            len(spec), c, bool(spec),                           # shape
            int(valid.sum()), self.token_budget,                # budget
            pool["blocks_free"], pool["blocks_private"],        # pool split
            pool["blocks_shared"], pool["blocks_cached_cold"],
            pool["trie_entries"])

    def _plan_spec(self, decode_lanes) -> dict[int, list[int]]:
        """Draft proposals for this step's decode lanes: {slot: tokens}.

        Per-lane k is clamped so the verify step never proposes past the
        request's remaining allowance (the correction/bonus token always
        fits) nor writes past the slot window. Both clamps and the
        proposals themselves are functions of the lane's OWN state, so
        spec scheduling stays batch-composition invariant — a lane drafts
        the same tokens whether it serves alone or in a full batch.
        Lanes clamped to k=0 fall back to plain 1-token decode."""
        if self.drafter is None:
            return {}
        spec = {}
        for s in decode_lanes:
            req = self.slot_req[s]
            lens0 = int(self.tables.lens[s])
            k = min(self.spec_k,
                    req.max_new_tokens - len(req.output) - 1,
                    self.max_len - 2 - lens0)
            if k > 0:
                drafts = self.drafter.propose(req.prompt + req.output, k)
                spec[s] = [int(t) for t in drafts]
        return spec

    def _apply_verify(self, s: int, rows, drafts: list[int], now: float):
        """Commit one lane's verify-step results.

        Walks the per-position target rows in plain-decode order (emission
        index = len(output)): each drafted token is accepted or replaced
        via exact rejection sampling (runtime.speculative.verify_token);
        the first rejection's row already yields the replacement, and a
        fully-accepted run earns the bonus token from the last row.
        Retirement checks (exhaustion / EOS / window-full) run after every
        emission exactly as the plain decode loop would. Rollback is free:
        kv_len is TRUNCATED to the committed prefix (prev token + matched
        drafts); rejected positions stay as garbage past kv_len until the
        next step's writes overwrite them — never readable, attention
        masks >= kv_len."""
        req = self.slot_req[s]
        lens0 = int(self.tables.lens[s])
        matched = emitted = 0
        retire = False
        self.metrics.spec_steps += 1
        self.metrics.draft_tokens += len(drafts)
        for i in range(len(drafts) + 1):
            idx = len(req.output)
            if i < len(drafts):
                tok, ok = verify_token(rows[i], drafts[i], req.sampling,
                                       idx)
            else:   # every draft matched: the bonus row is a free token
                tok, ok = sample_token(rows[i], req.sampling, idx), False
            req.output.append(int(tok))
            emitted += 1
            if ok:
                matched += 1
            self.metrics.decode_tokens += 1
            exhausted = len(req.output) >= req.max_new_tokens
            hit_eos = req.eos_id is not None and int(tok) == req.eos_id
            # plain-decode parity: before this emission the plain loop
            # would have written lens0 + emitted tokens and checked
            # lens + 1 against max_len - 1
            full = lens0 + emitted + 1 >= self.max_len - 1
            if exhausted or hit_eos or full:
                retire = True
                break
            if not ok:
                break
        self.metrics.draft_accepted += matched
        self.metrics.accept_hist[matched] = \
            self.metrics.accept_hist.get(matched, 0) + 1
        self.telemetry.spec_verify(req.rid, s, now, drafted=len(drafts),
                                   accepted=matched, emitted=emitted)
        self.telemetry.emission(req.rid, s, now, tokens=emitted)
        # rollback-by-truncation: the committed K/V covers the fed prev
        # token plus the matched drafts; everything past that is garbage
        self.tables.lens[s] = lens0 + 1 + matched
        if retire:
            self._retire_paged(s, now)

    def _register_prefix(self, slot: int):
        """Cache the completed prefill's full prompt blocks in the trie so
        later requests (and this one, if preempted) map them for free."""
        if self.trie is None:
            return
        src = self._pf_src[slot]
        nfull = len(src) // self.block_size
        if nfull:
            self.trie.insert(src[:nfull * self.block_size],
                             self.tables.held(slot)[:nfull], self.alloc)

    def _stash_forks(self, slot: int):
        """Parent prefill just completed: reference its whole block chain
        once per clone and queue the clones (front — they need zero new
        blocks, so they never block on the watermark)."""
        req = self.slot_req[slot]
        kids = self._fork_children.pop(req.rid, None)
        if not kids:
            return
        held = self.tables.held(slot)
        for c_req in reversed(kids):
            self.alloc.incref(held)
            self._fork_ready[c_req.rid] = {
                "blocks": list(held),
                "lens": int(self.tables.lens[slot]),
                "output": list(req.output)}
            self.queue.insert(0, c_req)

    def _preempt(self, slot: int):
        """Evict a running lane under pool pressure: register its full
        blocks in the trie (so resume re-maps instead of recomputing),
        release its refs, and re-queue it at the head with prompt +
        generated-so-far as the effective prompt. Greedy decode makes the
        resumed stream bit-identical to the unpreempted one."""
        req = self.slot_req[slot]
        lens = int(self.tables.lens[slot])
        if self.trie is not None and lens >= self.block_size:
            nfull = lens // self.block_size
            stream = (req.prompt + req.output)[:nfull * self.block_size]
            self.trie.insert(stream, self.tables.held(slot)[:nfull],
                             self.alloc)
        self.tables.release(slot, self.alloc)
        self.slot_req[slot] = None
        self._pf_src[slot] = None
        self._pf_done[slot] = 0
        self.queue.insert(0, req)
        self.metrics.preemptions += 1
        self._preempted_rids.add(req.rid)
        self.telemetry.preempt(req.rid, slot, self.telemetry.now(),
                               tokens_done=len(req.output))

    def _retire_paged(self, slot: int, now: float):
        req = self.slot_req[slot]
        req.done = True
        req.t_done = now
        self.telemetry.retire(req.rid, slot, now, tokens=len(req.output),
                              latency_s=req.latency_s)
        self.tables.release(slot, self.alloc)
        self.slot_req[slot] = None
        self._pf_src[slot] = None
        self._pf_done[slot] = 0

    def run_until_drained(self, max_steps: int = 10_000):
        while any(self.slot_req) or self.queue:
            before = self.steps_run
            self.step()
            if self.steps_run == before:
                # nothing was active; only admission can make progress
                self._admit()
                if not any(self.slot_req):
                    raise RuntimeError(
                        "admission stalled with an empty batch — the head "
                        "request cannot fit (submit-time checks should "
                        "have rejected it)")
            if self.steps_run > max_steps:
                raise RuntimeError("serving loop did not drain")

    # -- capacity / reporting -------------------------------------------------
    def _pool_stats(self) -> dict:
        """KV-pool composition split (paged engine only).

        `blocks_shared` counts refcount >= 2 blocks (live prefix sharing /
        fork reuse), `blocks_cached_cold` counts blocks whose ONLY
        reference is the trie (evictable cold prefix cache), and
        `blocks_private` is the remainder of in-use blocks — held by
        exactly one live lane. shared + cached_cold + private + free ==
        blocks_total."""
        st = self.alloc.stats
        cold = self.trie.cached_cold(self.alloc) \
            if self.trie is not None else 0
        return {"blocks_total": st.num_blocks,
                "blocks_free": st.free,
                "blocks_shared": st.shared,
                "blocks_cached_cold": cold,
                "blocks_private": st.private - cold,
                "trie_entries": self.trie.cached_blocks
                if self.trie is not None else 0}

    def flush_prefix_cache(self) -> int:
        """Drop every trie entry; blocks still mapped by a live slot just
        lose their cache ref. Returns blocks freed to the pool."""
        if self.paged and self.trie is not None:
            return self.trie.flush(self.alloc)
        return 0

    def kv_cache_bytes(self) -> dict:
        """Resident KV bytes: {"total": pool/cache footprint, "in_use":
        bytes of blocks currently referenced — live request blocks plus
        trie-cached (evictable) prefixes; == total for the slot cache}."""
        leaves = jax.tree_util.tree_leaves(self.cache)
        total = int(sum(a.nbytes for a in leaves
                        if hasattr(a, "nbytes") and a.ndim > 0))
        if not self.paged:
            return {"total": total, "in_use": total}
        nb = self.alloc.stats.num_blocks + 1     # pool includes trash block
        per_block = total // nb
        return {"total": total,
                "in_use": per_block * self.alloc.stats.in_use}


def _splice(batched_cache, request_cache, slot: int):
    """Insert a 1-deep request cache into the batched cache at `slot`.

    Both caches share the layout produced by init_cache / prefill; every
    array's batch axis is axis 1 for stacked [L, B, ...] entries. Scalars
    ("pos") take the max so the shared clock covers the deepest slot.
    """
    def one(dst, src):
        if dst.ndim == 0:
            return jnp.maximum(dst, src).astype(dst.dtype)
        # request caches have batch=1 at the same axis as dst's B
        axis = 1 if dst.ndim > 1 else 0
        start = [0] * dst.ndim
        start[axis] = slot
        src = src.astype(dst.dtype)
        if src.shape[axis] != 1:
            src = jnp.take(src, jnp.arange(1), axis=axis)
        # pad/trim sequence axes to dst
        for ax in range(dst.ndim):
            if ax != axis and src.shape[ax] != dst.shape[ax]:
                if src.shape[ax] < dst.shape[ax]:
                    pad = [(0, 0)] * dst.ndim
                    pad[ax] = (0, dst.shape[ax] - src.shape[ax])
                    src = jnp.pad(src, pad)
                else:
                    src = jnp.take(src, jnp.arange(dst.shape[ax]), axis=ax)
        return jax.lax.dynamic_update_slice(dst, src, tuple(start))

    return jax.tree.map(one, batched_cache, request_cache)
