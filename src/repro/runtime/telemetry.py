"""Serving-stack telemetry: lifecycle event trace, step snapshots, histograms.

This module is the in-process observability core for the paged serving
engine.  It is deliberately stdlib-only (no jax / numpy / repro imports) so
every layer of the stack — ``core.engine`` trace-time hooks included — can
import it without creating a cycle.

Three kinds of state live here:

* **Event trace** — a ring-buffered sequence of structured per-request
  lifecycle events (submit, admit, prefill_chunk, first_token, decode,
  spec_verify, cow_fork, preempt, resume, retire).  Timestamps come from an
  injectable monotonic clock so tests can drive a deterministic fake.
* **Step snapshots** — one :class:`StepSnapshot` per scheduler ``step()``
  sampling pool composition (free / private / shared / cached-cold blocks),
  prefix-trie size, token-budget utilization, lane counts and which compiled
  shape (chunk width ``c``, all-logits or not) ran.
* **Histograms** — fixed-bucket :class:`Histogram` instances for TTFT, ITL,
  spec-decode accept length and step wall time, with p50/p90/p99 estimation
  by linear interpolation inside the winning bucket.
* **Spans** — :meth:`Telemetry.span` times one phase of the host's work
  (``serve.plan`` … ``serve.admit`` inside a paged step, ``serve.build`` and
  its children at construction, ``serve.submit``) on the telemetry clock,
  and opens a profiler annotation of the same name so the phase also lands
  in a device trace, on the profiler's clock.  The annotation class is
  injected (the Server passes ``jax.profiler.TraceAnnotation``); the default
  is a no-op, which keeps this module free of jax.

Kernel/engine-layer counters (`KERNEL_COUNTERS`) are a process-wide
singleton because ``execute_mvm`` dispatch happens inside ``jax.jit``
tracing, far away from any `Server` instance.  Those counters count TRACED
calls — jit caching means one count per compiled shape, not one per executed
step — which is exactly what you want for "which backend did the dispatcher
pick" and "what would one traced step cost in CIM energy" questions, and is
documented on the class.  Its compile counter is the exception: it counts
real backend compiles and persistent-cache loads, fed by a
``jax.monitoring`` listener that ``runtime.server`` registers on import.

Exporters (Chrome trace-event JSON for Perfetto, Prometheus text
exposition, JSONL) live in :mod:`repro.runtime.obs`.
"""
from __future__ import annotations

import math
import time
from bisect import bisect_left
from collections import Counter, deque
from dataclasses import dataclass, field
from typing import NamedTuple

# ---------------------------------------------------------------------------
# histograms

# Bucket upper bounds (seconds unless noted).  Chosen to straddle both real
# wall clocks (ms..s on CPU jit) and the fake unit-step clocks tests inject.
TTFT_BUCKETS = (0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25, 0.5,
                1.0, 2.5, 5.0, 10.0, 30.0, 60.0)
ITL_BUCKETS = (0.0005, 0.001, 0.0025, 0.005, 0.01, 0.025, 0.05, 0.1, 0.25,
               0.5, 1.0, 2.5, 10.0)
STEP_BUCKETS = ITL_BUCKETS
# accepted draft tokens per verify step (counts, not seconds)
ACCEPT_BUCKETS = tuple(float(i) for i in range(9))


class Histogram:
    """Fixed-bucket histogram with percentile estimation.

    ``bounds`` are ascending bucket upper edges; an implicit +Inf bucket
    catches overflow.  Percentiles interpolate linearly inside the winning
    bucket, clamped to the observed min/max so single-sample histograms
    report the sample itself rather than a bucket edge.
    """

    def __init__(self, bounds):
        self.bounds = tuple(float(b) for b in bounds)
        if list(self.bounds) != sorted(set(self.bounds)):
            raise ValueError("histogram bounds must be strictly ascending")
        self.counts = [0] * (len(self.bounds) + 1)
        self.n = 0
        self.total = 0.0
        self.vmin = math.inf
        self.vmax = -math.inf

    def record(self, value: float) -> None:
        v = float(value)
        self.counts[bisect_left(self.bounds, v)] += 1
        self.n += 1
        self.total += v
        if v < self.vmin:
            self.vmin = v
        if v > self.vmax:
            self.vmax = v

    def record_many(self, values) -> None:
        """Bulk :meth:`record` — one bound-method call for a whole batch.

        The per-call dispatch is what shows up on the serving hot path
        (``decode_step`` records one ITL sample per lane per step), so the
        loop body binds the attributes once.
        """
        counts, bounds = self.counts, self.bounds
        vmin, vmax, total = self.vmin, self.vmax, self.total
        n = 0
        for value in values:
            v = float(value)
            counts[bisect_left(bounds, v)] += 1
            n += 1
            total += v
            if v < vmin:
                vmin = v
            if v > vmax:
                vmax = v
        self.n += n
        self.total = total
        self.vmin = vmin
        self.vmax = vmax

    def percentile(self, p: float) -> float:
        if self.n == 0:
            return 0.0
        target = max(1, math.ceil((p / 100.0) * self.n))
        cum = 0
        for i, c in enumerate(self.counts):
            if not c:
                continue
            cum += c
            if cum >= target:
                lo = self.bounds[i - 1] if i > 0 else self.vmin
                hi = self.bounds[i] if i < len(self.bounds) else self.vmax
                lo = max(lo, self.vmin)
                hi = min(hi, self.vmax)
                if hi <= lo:
                    return lo
                frac = (target - (cum - c)) / c
                return lo + frac * (hi - lo)
        return self.vmax  # pragma: no cover — unreachable

    def summary(self) -> dict:
        if self.n == 0:
            return {"count": 0}
        return {
            "count": self.n,
            "sum": self.total,
            "mean": self.total / self.n,
            "min": self.vmin,
            "max": self.vmax,
            "p50": self.percentile(50),
            "p90": self.percentile(90),
            "p99": self.percentile(99),
        }


# ---------------------------------------------------------------------------
# events + snapshots


# Per-request lifecycle event kinds, in canonical per-rid order:
#   submit -> admit -> prefill_chunk* -> first_token
#          -> (decode | spec_verify | cow_fork)*
#          -> (preempt -> resume -> prefill_chunk* ...)*  -> retire
EVENT_KINDS = frozenset({
    "submit", "admit", "resume", "prefill_chunk", "first_token", "decode",
    "spec_verify", "cow_fork", "preempt", "retire",
})


# Event and StepSnapshot are NamedTuples, not dataclasses: construction is
# on the decode hot path (one Event per emitted token, one StepSnapshot per
# step) and tuple construction is several times cheaper.
class Event(NamedTuple):
    """One structured trace event.  ``data`` holds kind-specific fields."""

    kind: str
    t: float
    rid: int = -1
    slot: int = -1
    data: dict | None = None

    def to_dict(self) -> dict:
        d = {"kind": self.kind, "t": self.t, "rid": self.rid,
             "slot": self.slot}
        if self.data:
            d.update(self.data)
        return d


#: The host phases of one paged ``step()``, in order; together they cover
#: the whole step.  Each is a ``serve.<phase>`` span (see Telemetry.span).
PHASES = ("plan", "device", "fetch", "sample", "admit")


class StepSnapshot(NamedTuple):
    """Scheduler/pool state sampled once per paged ``step()``.  ``t`` is
    the step's end and ``wall_s`` its whole duration, every phase
    included."""

    step: int
    t: float
    wall_s: float
    active: int
    decode_lanes: int
    prefill_lanes: int
    spec_lanes: int
    c: int                 # compiled chunk width this step ran with
    all_logits: bool       # True when the spec-verify compilation ran
    budget_used: int
    token_budget: int
    blocks_free: int
    blocks_private: int
    blocks_shared: int
    blocks_cached_cold: int
    trie_entries: int
    compiles: int          # KERNEL_COUNTERS.compile_events after the step
    phases: tuple          # seconds per PHASES entry, in that order

    def to_dict(self) -> dict:
        return {
            "kind": "step_snapshot", "step": self.step, "t": self.t,
            "wall_s": self.wall_s, "active": self.active,
            "decode_lanes": self.decode_lanes,
            "prefill_lanes": self.prefill_lanes,
            "spec_lanes": self.spec_lanes, "c": self.c,
            "all_logits": self.all_logits,
            "budget_used": self.budget_used,
            "token_budget": self.token_budget,
            "blocks_free": self.blocks_free,
            "blocks_private": self.blocks_private,
            "blocks_shared": self.blocks_shared,
            "blocks_cached_cold": self.blocks_cached_cold,
            "trie_entries": self.trie_entries,
            "compiles": self.compiles,
            "phases": dict(zip(PHASES, self.phases)),
        }


# ---------------------------------------------------------------------------
# kernel / engine counters


# jax.monitoring events the compile counter listens to
BACKEND_COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_LOAD_EVENT = "/jax/compilation_cache/cache_retrieval_time_sec"


@dataclass
class KernelCounters:
    """Process-wide engine/kernel dispatch + energy counters.

    IMPORTANT: the ``execute_mvm`` and paged-attention hooks fire at jax
    TRACE time.  Under ``jax.jit`` a traced function executes Python once
    per compiled shape, so these counters record *traced* calls (one per
    compilation), not per-step executions.  They answer "which backend did
    the dispatcher pick for each shape family" and "what does one traced
    step cost in CIM energy per weight site", not "how many MVMs ran".
    Host-side counters (drafter, tune-cache, fallback warnings) do count
    real calls, and so does the compile counter: :meth:`compile_event` is a
    ``jax.monitoring`` duration listener, so ``compiles`` / ``compile_s``
    count backend compiles and ``cache_loads`` / ``cache_load_s``
    executables loaded from the persistent compilation cache.  Each event
    is also logged with its end time on ``time.perf_counter``, so a reader
    can tell set-up compiles from those inside a measured window.
    """

    backend_dispatch: Counter = field(default_factory=Counter)
    attn_dispatch: Counter = field(default_factory=Counter)
    tune_cache: Counter = field(default_factory=Counter)
    fallback_warnings: int = 0
    drafter: Counter = field(default_factory=Counter)
    # site -> {"calls": traced execute_mvm calls, "dots": K-deep dot
    # products per traced call (rows x out-cols), "energy_j": paper-model
    # Eq.4 energy for those dots}
    site_energy: dict = field(default_factory=dict)
    compiles: int = 0
    compile_s: float = 0.0
    cache_loads: int = 0
    cache_load_s: float = 0.0
    # (t_end, "compile" | "cache_load", seconds), newest last
    compile_log: deque = field(default_factory=lambda: deque(maxlen=4096))
    _cache_hit: bool = False

    @property
    def compile_events(self) -> int:
        """Executables built or loaded so far: a step that raises this
        recompiled (or reloaded) something."""
        return self.compiles + self.cache_loads

    def compile_event(self, event: str, seconds: float, **_) -> None:
        """``jax.monitoring`` duration listener.  JAX times every
        ``compile_or_get_cached`` as one backend-compile event; a cache hit
        first reports its retrieval, so the backend event that follows is
        a load, not a compile."""
        if event == CACHE_LOAD_EVENT:
            self._cache_hit = True
            return
        if event != BACKEND_COMPILE_EVENT:
            return
        kind = "cache_load" if self._cache_hit else "compile"
        self._cache_hit = False
        if kind == "compile":
            self.compiles += 1
            self.compile_s += seconds
        else:
            self.cache_loads += 1
            self.cache_load_s += seconds
        self.compile_log.append((time.perf_counter(), kind, seconds))

    def count_backend(self, name: str) -> None:
        self.backend_dispatch[name] += 1

    def count_attn(self, name: str) -> None:
        self.attn_dispatch[name] += 1

    def tune_lookup(self, kernel: str, hit: bool) -> None:
        self.tune_cache[f"{kernel}:{'hit' if hit else 'miss'}"] += 1

    def count_fallback(self) -> None:
        self.fallback_warnings += 1

    def count_drafter(self, event: str) -> None:
        self.drafter[event] += 1

    def add_site_energy(self, site: str, energy_j: float, dots: int) -> None:
        rec = self.site_energy.setdefault(
            site, {"calls": 0, "dots": 0, "energy_j": 0.0})
        rec["calls"] += 1
        rec["dots"] += int(dots)
        rec["energy_j"] += float(energy_j)

    def snapshot(self) -> dict:
        return {
            "backend_dispatch": dict(self.backend_dispatch),
            "attn_dispatch": dict(self.attn_dispatch),
            "tune_cache": dict(self.tune_cache),
            "fallback_warnings": self.fallback_warnings,
            "drafter": dict(self.drafter),
            "site_energy": {k: dict(v) for k, v in self.site_energy.items()},
            "compiles": self.compiles,
            "compile_s": self.compile_s,
            "cache_loads": self.cache_loads,
            "cache_load_s": self.cache_load_s,
        }

    def reset(self) -> None:
        self.backend_dispatch.clear()
        self.attn_dispatch.clear()
        self.tune_cache.clear()
        self.fallback_warnings = 0
        self.drafter.clear()
        self.site_energy.clear()
        self.compiles = self.cache_loads = 0
        self.compile_s = self.cache_load_s = 0.0
        self.compile_log.clear()
        self._cache_hit = False


#: Singleton the engine/kernel hooks write into.  Reset via
#: ``KERNEL_COUNTERS.reset()`` (tests) — serving code only reads it.
KERNEL_COUNTERS = KernelCounters()


# ---------------------------------------------------------------------------
# per-server telemetry


# Pending-buffer auto-flush threshold: bounds memory between reads while
# keeping the replay pass far off the per-step hot path (~3 ops/step, so a
# mid-serve flush fires once per ~1400 steps — a GC-pause-scale hiccup).
_FLUSH_AT = 4096


class Telemetry:
    """Per-:class:`~repro.runtime.server.Server` telemetry sink.

    ``clock`` is any zero-arg callable returning monotonic seconds; tests
    inject a deterministic fake.  With ``enabled=False`` every recording
    method early-returns after serving the clock, so the telemetry-off
    overhead is one attribute check per call site.

    Recording is TWO-PHASE.  The hooks the Server calls from inside
    ``step()`` do nothing but append one small raw tuple to a pending
    list — on a serving step measured in milliseconds every Python
    operation spent aggregating would land directly on TTFT/ITL, and
    in-situ (cold-cache, right after the jitted step) each op costs
    several times its microbenchmark price.  The aggregation — Event
    construction, ring append, histogram bucketing, per-rid ITL marks —
    happens in :meth:`_flush`, which replays the raw tuples in order.
    Every read surface (``events``, ``snapshots``, ``counters``, the
    histograms, :meth:`summary`) is a property/method that flushes
    first, so readers never observe the buffering; a size threshold
    (``_FLUSH_AT``) bounds pending memory on export-free runs; the
    deferred replay runs at export time (or amortised ~once per 1400
    steps mid-serve).

    :meth:`span` is the one timing primitive: ``with tel.span("plan"):``
    opens the profiler annotation ``serve.plan`` (``annotation`` is the
    injected class, a no-op by default) and, when enabled, stores the
    phase's duration.  The five :data:`PHASES` land in that step's
    :class:`StepSnapshot`; every other span name accumulates a count and
    seconds, reported by :meth:`summary` under ``"spans"``.
    """

    def __init__(self, *, enabled: bool = True, clock=time.monotonic,
                 capacity: int = 65536, snapshot_capacity: int = 16384):
        self.enabled = bool(enabled)
        self.clock = clock
        self._events: deque[Event] = deque(maxlen=capacity)
        self._snapshots: deque[StepSnapshot] = deque(
            maxlen=snapshot_capacity)
        self._counters: Counter = Counter()  # total events by kind (no cap)
        self._ttft = Histogram(TTFT_BUCKETS)
        self._itl = Histogram(ITL_BUCKETS)
        self._accept_len = Histogram(ACCEPT_BUCKETS)
        self._step_wall = Histogram(STEP_BUCKETS)
        self.kernel = KERNEL_COUNTERS
        self.annotation = _NoAnnotation
        # compile events before the first snapshot (the exporter's baseline)
        self.compiles_at_start = self.kernel.compile_events
        self._phase_s = [0.0] * len(PHASES)     # this step's phases
        self._span_s: dict[str, list] = {}      # other spans: [n, seconds]
        self._last_emit: dict[int, float] = {}   # rid -> t (replay state)
        self._pending: list[tuple] = []
        self._replay = {
            "event": self._rp_event, "submit": self._rp_submit,
            "admit": self._rp_admit, "prefill_chunk": self._rp_prefill,
            "first_token": self._rp_first_token,
            "emission": self._rp_emission, "decode": self._rp_decode_step,
            "spec_verify": self._rp_spec_verify, "retire": self._rp_retire,
            "snap": self._rp_snap,
        }

    # -- clock ------------------------------------------------------------
    def now(self) -> float:
        return self.clock()

    # -- spans ------------------------------------------------------------
    def span(self, name: str, **args) -> "_Span":
        """Time one phase: a ``serve.<name>`` profiler annotation (``args``
        become its metadata) and, when enabled, its duration on the
        telemetry clock."""
        return _Span(self, name, self.annotation("serve." + name, **args))

    def _end_span(self, name: str, seconds: float) -> None:
        i = _PHASE_INDEX.get(name)
        if i is not None:
            self._phase_s[i] = seconds
            return
        rec = self._span_s.get(name)
        if rec is None:
            rec = self._span_s[name] = [0, 0.0]
        rec[0] += 1
        rec[1] += seconds

    # -- hot-phase hooks (called by Server; append raw tuples only) -------
    def event(self, kind: str, rid: int = -1, slot: int = -1,
              t: float | None = None, **data) -> None:
        """Generic event.  ``t=None`` stamps the clock NOW (not at flush)."""
        if not self.enabled:
            return
        p = self._pending
        p.append(("event", kind, rid, slot,
                  self.clock() if t is None else t, data or None))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def submit(self, rid: int, t: float, prompt_len: int,
               n_samples: int) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("submit", rid, t, prompt_len, n_samples))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def admit(self, rid: int, slot: int, t: float, *, prefix_hit_blocks: int,
              prefill_tokens: int, resume: bool = False,
              fork: bool = False) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("admit", rid, slot, t, prefix_hit_blocks, prefill_tokens,
                  resume, fork))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def prefill_chunk(self, rid: int, slot: int, t: float, tokens: int,
                      done: int, total: int) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("prefill_chunk", rid, slot, t, tokens, done, total))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def first_token(self, rid: int, slot: int, t: float,
                    t_submit: float) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("first_token", rid, slot, t, t_submit))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def emission(self, rid: int, slot: int, t: float,
                 tokens: int = 1) -> None:
        """One token emission outside the batched plain-decode path.

        Used by spec-verify (multi-token: the ITL sample is the per-token
        effective latency ``(t - last) / tokens``, the quantity
        speculative decoding improves), resume completions, and the
        legacy slot engine.  Plain decode uses :meth:`decode_step`.
        """
        if not self.enabled:
            return
        p = self._pending
        p.append(("emission", rid, slot, t, tokens))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def decode_step(self, lanes: list, t: float) -> None:
        """Batched decode emissions: ``lanes`` is ``[(rid, slot), ...]``.

        The hottest hook — one call per paged ``step()`` covering every
        plain-decode lane.  Per-lane ITL samples and ``decode`` counter
        semantics are preserved at replay, but the ring gets a SINGLE
        event carrying the lane list (``data={"lanes": [...]}``; rid/slot
        stamp the first lane).  ``obs.chrome_trace`` expands it back into
        one instant per lane, so the exported trace is unchanged.
        """
        if not self.enabled or not lanes:
            return
        p = self._pending
        p.append(("decode", lanes, t))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def spec_verify(self, rid: int, slot: int, t: float, *, drafted: int,
                    accepted: int, emitted: int) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("spec_verify", rid, slot, t, drafted, accepted, emitted))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def cow_fork(self, rid: int, slot: int, t: float, src_block: int,
                 dst_block: int) -> None:
        self.event("cow_fork", rid, slot, t, src_block=src_block,
                   dst_block=dst_block)

    def preempt(self, rid: int, slot: int, t: float,
                tokens_done: int) -> None:
        self.event("preempt", rid, slot, t, tokens_done=tokens_done)

    def retire(self, rid: int, slot: int, t: float, *, tokens: int,
               latency_s: float | None) -> None:
        if not self.enabled:
            return
        p = self._pending
        p.append(("retire", rid, slot, t, tokens, latency_s))
        if len(p) >= _FLUSH_AT:
            self._flush()

    def step_snapshot(self, step, t, wall_s, active, decode_lanes,
                      prefill_lanes, spec_lanes, c, all_logits, budget_used,
                      token_budget, blocks_free, blocks_private,
                      blocks_shared, blocks_cached_cold,
                      trie_entries) -> None:
        """Record the step; stamps the compile count and the step's phase
        durations itself, so call it after the step's last span."""
        # explicit parameter list (not **kw): the kwargs repack is the
        # costliest part of a per-step call, and the off leg pays it too
        if not self.enabled:
            return
        p = self._pending
        p.append(("snap", step, t, wall_s, active, decode_lanes,
                  prefill_lanes, spec_lanes, c, all_logits, budget_used,
                  token_budget, blocks_free, blocks_private, blocks_shared,
                  blocks_cached_cold, trie_entries,
                  self.kernel.compile_events, tuple(self._phase_s)))
        if len(p) >= _FLUSH_AT:
            self._flush()

    # -- replay (aggregation) phase ---------------------------------------
    def _flush(self) -> None:
        """Replay pending raw tuples, in order, into the read structures."""
        pending = self._pending
        if not pending:
            return
        self._pending = []
        replay = self._replay
        for op in pending:
            replay[op[0]](*op[1:])

    def _rp_event(self, kind, rid, slot, t, data) -> None:
        self._counters[kind] += 1
        self._events.append(Event(kind, t, rid, slot, data))

    def _rp_submit(self, rid, t, prompt_len, n_samples) -> None:
        self._rp_event("submit", rid, -1, t,
                       {"prompt_len": prompt_len, "n_samples": n_samples})

    def _rp_admit(self, rid, slot, t, prefix_hit_blocks, prefill_tokens,
                  resume, fork) -> None:
        data = {"prefix_hit_blocks": prefix_hit_blocks,
                "prefill_tokens": prefill_tokens}
        if fork:
            data["fork"] = True
        self._rp_event("resume" if resume else "admit", rid, slot, t, data)

    def _rp_prefill(self, rid, slot, t, tokens, done, total) -> None:
        self._rp_event("prefill_chunk", rid, slot, t,
                       {"tokens": tokens, "done": done, "total": total})

    def _rp_first_token(self, rid, slot, t, t_submit) -> None:
        ttft = t - t_submit
        self._ttft.record(ttft)
        self._last_emit[rid] = t
        self._rp_event("first_token", rid, slot, t, {"ttft_s": ttft})

    def _rp_emission(self, rid, slot, t, tokens) -> None:
        last = self._last_emit.get(rid)
        if last is not None and t >= last:
            self._itl.record((t - last) / tokens if tokens > 1
                             else t - last)
        self._last_emit[rid] = t
        self._rp_event("decode", rid, slot, t, {"tokens": tokens})

    def _rp_decode_step(self, lanes, t) -> None:
        last = self._last_emit
        samples = []
        for rid, _slot in lanes:
            lt = last.get(rid)
            if lt is not None and t >= lt:
                samples.append(t - lt)
            last[rid] = t
        if samples:
            self._itl.record_many(samples)
        self._counters["decode"] += len(lanes)
        rid0, slot0 = lanes[0]
        self._events.append(Event("decode", t, rid0, slot0,
                                  {"lanes": lanes}))

    def _rp_spec_verify(self, rid, slot, t, drafted, accepted,
                        emitted) -> None:
        self._accept_len.record(accepted)
        self._rp_event("spec_verify", rid, slot, t,
                       {"drafted": drafted, "accepted": accepted,
                        "emitted": emitted})

    def _rp_retire(self, rid, slot, t, tokens, latency_s) -> None:
        self._last_emit.pop(rid, None)
        self._rp_event("retire", rid, slot, t,
                       {"tokens": tokens, "latency_s": latency_s})

    def _rp_snap(self, *fields) -> None:
        self._step_wall.record(fields[2])        # wall_s
        self._snapshots.append(StepSnapshot(*fields))

    # -- read surfaces (flush first, so buffering is never observable) ----
    @property
    def events(self) -> deque:
        self._flush()
        return self._events

    @property
    def snapshots(self) -> deque:
        self._flush()
        return self._snapshots

    @property
    def counters(self) -> Counter:
        self._flush()
        return self._counters

    @property
    def ttft(self) -> Histogram:
        self._flush()
        return self._ttft

    @property
    def itl(self) -> Histogram:
        self._flush()
        return self._itl

    @property
    def accept_len(self) -> Histogram:
        self._flush()
        return self._accept_len

    @property
    def step_wall(self) -> Histogram:
        self._flush()
        return self._step_wall

    # -- management -------------------------------------------------------
    def reset(self) -> None:
        """Clear per-run state (events, snapshots, histograms, ITL marks,
        span totals).

        Does NOT touch :data:`KERNEL_COUNTERS` — that singleton is shared
        across servers and owned by whoever resets it explicitly.
        """
        self._pending.clear()
        self._events.clear()
        self._snapshots.clear()
        self._counters.clear()
        self._span_s.clear()
        self.compiles_at_start = self.kernel.compile_events
        self._last_emit.clear()
        self._ttft = Histogram(TTFT_BUCKETS)
        self._itl = Histogram(ITL_BUCKETS)
        self._accept_len = Histogram(ACCEPT_BUCKETS)
        self._step_wall = Histogram(STEP_BUCKETS)

    def summary(self) -> dict:
        self._flush()
        return {
            "events": dict(self._counters),
            "ttft": self._ttft.summary(),
            "itl": self._itl.summary(),
            "accept_len": self._accept_len.summary(),
            "step_wall": self._step_wall.summary(),
            "spans": {k: {"count": n, "seconds": sec}
                      for k, (n, sec) in self._span_s.items()},
            "kernel": self.kernel.snapshot(),
        }


class _NoAnnotation:
    """Default profiler annotation: does nothing (the Server injects
    ``jax.profiler.TraceAnnotation``)."""

    __slots__ = ()

    def __init__(self, name: str, **args):
        pass

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_PHASE_INDEX = {p: i for i, p in enumerate(PHASES)}


class _Span:
    """Context manager :meth:`Telemetry.span` returns."""

    __slots__ = ("tel", "name", "ann", "t0")

    def __init__(self, tel: Telemetry, name: str, ann):
        self.tel, self.name, self.ann = tel, name, ann

    def __enter__(self):
        self.ann.__enter__()
        if self.tel.enabled:
            self.t0 = self.tel.clock()
        return self

    def __exit__(self, *exc):
        tel = self.tel
        if tel.enabled:
            tel._end_span(self.name, tel.clock() - self.t0)
        self.ann.__exit__(*exc)
        return False
