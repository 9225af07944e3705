"""runtime.telemetry / runtime.obs: deterministic-clock lifecycle tracing,
histogram percentiles, exporter structure, and the metrics/trace
consistency soak.

The soak drives the PR-10 acceptance schedule — a pool sized so three
concurrent mixed-depth requests MUST preempt, with the ngram drafter on —
through a Server carrying a fake monotonic clock, then asserts the trace
invariants the ISSUE pins:

  * every `admit` is closed by a `retire` or continued by a
    `preempt` → `resume` chain (per rid, in order);
  * TTFT (first_token time) >= the request's first prefill_chunk time;
  * Σ accept_hist counts == spec_steps, in ServerMetrics AND in the
    telemetry accept-length histogram;
  * ServerMetrics.to_dict() exposes the shared/private/cached-cold pool
    split + trie entry count, and the split sums to the pool size;
  * the Chrome trace validates against runtime.obs.validate_chrome_trace
    and the Prometheus snapshot carries the expected metric families.

Runs identically under both REPRO_FORCE_JNP legs (attn="exact" is pinned,
so the compiled math is leg-independent).
"""
import itertools
import json

import jax
import numpy as np
import pytest

from repro.configs.registry import SMOKES
from repro.models import registry
from repro.runtime import obs
from repro.runtime.server import Request, Server, ServingConfig
from repro.runtime.telemetry import (ACCEPT_BUCKETS, BACKEND_COMPILE_EVENT,
                                     CACHE_LOAD_EVENT, KERNEL_COUNTERS,
                                     PHASES, Histogram, KernelCounters,
                                     Telemetry)


class FakeClock:
    """Deterministic monotonic clock: each call advances by `tick`."""

    def __init__(self, tick: float = 0.125):
        self.t = 0.0
        self.tick = tick

    def __call__(self) -> float:
        self.t += self.tick
        return self.t


# ---------------------------------------------------------------------------
# histogram unit tests


def test_histogram_percentiles_interpolate():
    h = Histogram((1.0, 2.0, 4.0, 8.0))
    for v in (0.5, 1.5, 1.5, 3.0, 6.0, 9.0):
        h.record(v)
    s = h.summary()
    assert s["count"] == 6
    assert s["sum"] == pytest.approx(21.5)
    assert s["min"] == 0.5 and s["max"] == 9.0
    # percentiles stay within the observed range and are monotone
    ps = [h.percentile(p) for p in (1, 25, 50, 75, 90, 99)]
    assert all(0.5 <= v <= 9.0 for v in ps)
    assert ps == sorted(ps)


def test_histogram_single_sample_reports_itself():
    h = Histogram((1.0, 10.0))
    h.record(3.0)
    assert h.percentile(50) == pytest.approx(3.0)
    assert h.percentile(99) == pytest.approx(3.0)


def test_histogram_empty_and_bad_bounds():
    assert Histogram((1.0,)).percentile(50) == 0.0
    with pytest.raises(ValueError):
        Histogram((2.0, 1.0))


def test_decode_step_batches_lanes():
    """decode_step: per-lane ITL samples + counters, ONE ring event."""
    clock = FakeClock()
    tel = Telemetry(clock=clock)
    t1 = tel.now()
    tel.first_token(7, 0, t1, 0.0)
    tel.first_token(9, 1, t1, 0.0)
    t2 = tel.now()
    tel.decode_step([(7, 0), (9, 1)], t2)
    assert tel.counters["decode"] == 2
    assert tel.itl.n == 2                       # one ITL sample per lane
    assert tel.itl.vmin == pytest.approx(t2 - t1)
    ev = [e for e in tel.events if e.kind == "decode"]
    assert len(ev) == 1                         # batched into one event
    assert ev[0].data["lanes"] == [(7, 0), (9, 1)]
    assert (ev[0].rid, ev[0].slot) == (7, 0)
    tel.decode_step([], tel.now())              # no-op, no empty event
    assert len([e for e in tel.events if e.kind == "decode"]) == 1
    # the chrome exporter expands the batch back to one instant per lane
    doc = obs.chrome_trace(tel)
    inst = [x for x in doc["traceEvents"]
            if x.get("name") == "decode" and x["ph"] == "i"]
    assert len(inst) == 2
    assert {x["tid"] for x in inst} == {1, 2}   # slot tracks 0+1, 1+1
    assert obs.validate_chrome_trace(doc) == []


def test_telemetry_disabled_records_nothing():
    clock = FakeClock()
    tel = Telemetry(enabled=False, clock=clock)
    tel.submit(0, tel.now(), 4, 1)
    tel.first_token(0, 0, tel.now(), 0.0)
    tel.emission(0, 0, tel.now())
    assert not tel.events and tel.ttft.n == 0 and tel.itl.n == 0
    # the clock still serves (the Server's wall timing shares it)
    assert tel.now() > 0


# ---------------------------------------------------------------------------
# the consistency soak


@pytest.fixture(scope="module")
def soak():
    """Mixed-depth preemption + spec-decode drain with a fake clock.

    Pool math: block_size=4, max_len=32 → 8 blocks/slot worst case; 10
    usable blocks with 3 slots and ~15-block worst-case demand forces
    newest-victim preemption while the ngram drafter runs verify steps."""
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    serving = ServingConfig(n_slots=3, max_len=32, paged=True, block_size=4,
                            num_blocks=10, prefill_chunk=4, attn="exact",
                            drafter="ngram", spec_k=2)
    clock = FakeClock()
    srv = Server(params, cfg, serving, telemetry=Telemetry(clock=clock))
    rng = np.random.RandomState(0)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=6 + i).tolist(),
                    max_new_tokens=8) for i in range(5)]
    for r in reqs:
        srv.submit(r)
    srv.run_until_drained()
    return srv, reqs


def test_soak_preempts_and_speculates(soak):
    srv, _ = soak
    assert srv.metrics.preemptions >= 1, "schedule must exercise preemption"
    assert srv.metrics.spec_steps >= 1, "schedule must exercise spec decode"


def test_soak_admit_chains(soak):
    """Every admit is closed by retire or continued by preempt→resume."""
    srv, reqs = soak
    by_rid: dict[int, list] = {}
    for e in srv.telemetry.events:
        if e.kind in ("admit", "resume", "preempt", "retire"):
            by_rid.setdefault(e.rid, []).append(e.kind)
    assert set(by_rid) == {r.rid for r in reqs}
    for rid, kinds in by_rid.items():
        assert kinds[0] == "admit" and kinds[-1] == "retire", (rid, kinds)
        # interior transitions: admit/resume opens, preempt closes+reopens
        open_ = False
        for k in kinds:
            if k in ("admit", "resume"):
                assert not open_, (rid, kinds)
                open_ = True
            elif k == "preempt":
                assert open_, (rid, kinds)
                open_ = False
            else:   # retire
                assert open_, (rid, kinds)
                open_ = False
        assert not open_, (rid, kinds)


def test_soak_resume_follows_preempt(soak):
    srv, _ = soak
    c = srv.telemetry.counters
    assert c["preempt"] == srv.metrics.preemptions
    # every preempted request came back (the drain completed), and a
    # resume only ever follows a preempt
    assert c["resume"] == c["preempt"]


def test_soak_ttft_after_first_chunk(soak):
    """first_token time >= the rid's first prefill_chunk time."""
    srv, _ = soak
    first_chunk: dict[int, float] = {}
    for e in srv.telemetry.events:
        if e.kind == "prefill_chunk" and e.rid not in first_chunk:
            first_chunk[e.rid] = e.t
        if e.kind == "first_token":
            assert e.rid in first_chunk, "first_token before any chunk"
            assert e.t >= first_chunk[e.rid]
            assert e.data["ttft_s"] > 0


def test_soak_accept_hist_totals(soak):
    """Σ accept_hist == spec_steps — metrics bag and telemetry agree."""
    srv, _ = soak
    m = srv.metrics.summary()
    assert sum(m["accept_hist"].values()) == m["spec_steps"]
    assert srv.telemetry.accept_len.n == m["spec_steps"]
    assert srv.telemetry.counters["spec_verify"] == m["spec_steps"]
    # accepted-draft totals agree too (hist is over accepted counts)
    assert sum(a * n for a, n in m["accept_hist"].items()) \
        == m["draft_accepted"]


def test_soak_pool_split_in_to_dict(soak):
    srv, _ = soak
    d = srv.metrics.to_dict()
    for key in ("blocks_total", "blocks_free", "blocks_shared",
                "blocks_cached_cold", "blocks_private", "trie_entries"):
        assert key in d, key
    assert (d["blocks_free"] + d["blocks_shared"] + d["blocks_cached_cold"]
            + d["blocks_private"]) == d["blocks_total"]
    # drained server: nothing live, so in-use blocks are all cold cache
    assert d["blocks_private"] == 0 and d["blocks_shared"] == 0
    assert d["trie_entries"] == d["blocks_cached_cold"]


def test_soak_step_snapshots(soak):
    srv, _ = soak
    snaps = list(srv.telemetry.snapshots)
    assert len(snaps) == srv.metrics.steps
    assert all(s.wall_s > 0 for s in snaps)
    assert any(s.all_logits and s.c == srv.spec_k + 1 for s in snaps), \
        "spec verify steps must stamp the C=k+1 all-logits shape"
    assert any(s.prefill_lanes for s in snaps)
    for s in snaps:
        assert s.budget_used > 0
        # the token budget gates prefill scheduling; spec-verify steps
        # legitimately exceed it (each spec lane runs k+1 positions)
        if not s.all_logits:
            assert s.budget_used <= s.token_budget
        assert (s.blocks_free + s.blocks_shared + s.blocks_cached_cold
                + s.blocks_private) == 10
    # snapshot times strictly increase with the fake clock
    ts = [s.t for s in snaps]
    assert ts == sorted(ts)


def test_soak_chrome_trace_valid(soak, tmp_path):
    srv, _ = soak
    doc = obs.chrome_trace(srv.telemetry)
    assert obs.validate_chrome_trace(doc) == []
    names = {ev.get("name") for ev in doc["traceEvents"]}
    assert "scheduler" in json.dumps(doc)   # scheduler track named
    assert any(n and n.startswith("req") for n in names)
    assert any(n and n.startswith("step") for n in names)
    # round-trips through the CLI validator
    p = tmp_path / "trace.json"
    p.write_text(json.dumps(doc))
    assert obs.main([str(p)]) == 0
    # and the validator actually rejects structural damage
    doc["traceEvents"].append({"ph": "Q", "ts": 0})
    assert obs.validate_chrome_trace(doc)


def test_soak_prometheus_snapshot(soak):
    srv, _ = soak
    text = obs.prometheus_text(srv.telemetry, srv)
    for needle in ("picoram_ttft_seconds_bucket{le=",
                   "picoram_ttft_seconds_count",
                   "picoram_itl_seconds_sum",
                   "picoram_accept_length_bucket",
                   "picoram_step_wall_seconds_count",
                   'picoram_events_total{kind="admit"}',
                   'picoram_attn_dispatch_total{backend="exact"}',
                   'picoram_kv_blocks{state="cached_cold"}',
                   "picoram_trie_entries"):
        assert needle in text, needle
    # cumulative histogram buckets are monotone
    cum = [int(ln.rsplit(" ", 1)[1]) for ln in text.splitlines()
           if ln.startswith("picoram_ttft_seconds_bucket")]
    assert cum == sorted(cum)


def test_soak_events_jsonl(soak, tmp_path):
    srv, _ = soak
    p = tmp_path / "events.jsonl"
    n = obs.write_events_jsonl(srv.telemetry, str(p))
    lines = [json.loads(ln) for ln in p.read_text().splitlines()]
    assert len(lines) == n
    kinds = {ln["kind"] for ln in lines}
    assert {"submit", "admit", "retire", "step_snapshot"} <= kinds


def test_telemetry_off_serves_identically():
    """ServingConfig(telemetry=False) changes nothing but observability."""
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    outs = []
    for on in (True, False):
        srv = Server(params, cfg, ServingConfig(
            n_slots=2, max_len=32, paged=True, block_size=4,
            prefill_chunk=4, attn="exact", telemetry=on))
        rng = np.random.RandomState(1)
        reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=5).tolist(),
                        max_new_tokens=6) for _ in range(3)]
        for r in reqs:
            srv.submit(r)
        srv.run_until_drained()
        outs.append([r.output for r in reqs])
        if on:
            assert srv.telemetry.events
            assert all(len(s.phases) == len(PHASES)
                       for s in srv.telemetry.snapshots)
        else:
            assert not srv.telemetry.events and srv.telemetry.ttft.n == 0
            # no phases, spans or snapshots are recorded either
            assert not srv.telemetry.snapshots
            assert srv.telemetry.summary()["spans"] == {}
            # the pool split still lands on the metrics bag
            assert "blocks_free" in srv.metrics.to_dict()
    assert outs[0] == outs[1]


def test_legacy_engine_emits_lifecycle():
    """The slot engine traces admit/first_token/decode/retire too."""
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    srv = Server(params, cfg, ServingConfig(n_slots=2, max_len=32),
                 telemetry=Telemetry(clock=FakeClock()))
    srv.submit(Request(prompt=[3, 1, 4, 1, 5], max_new_tokens=4))
    srv.run_until_drained()
    c = srv.telemetry.counters
    assert c["submit"] == c["admit"] == c["first_token"] == c["retire"] == 1
    assert srv.telemetry.ttft.n == 1
    assert obs.validate_chrome_trace(
        obs.chrome_trace(srv.telemetry)) == []


def test_kernel_counters_site_energy():
    """execute_mvm's trace-time hook accumulates per-site CIM energy
    keyed by the PR-9 site names and counts the backend pick."""
    import jax.numpy as jnp
    from repro.core.cim_matmul import CIMConfig, cim_matmul
    from repro.core.quant import act_site
    from repro.runtime.telemetry import KERNEL_COUNTERS

    KERNEL_COUNTERS.reset()
    cim = CIMConfig(enabled=True)
    x = jnp.linspace(0.0, 1.0, 2 * 16).reshape(2, 16)
    w = jnp.linspace(-1.0, 1.0, 16 * 8).reshape(16, 8)
    with act_site("wq"):
        cim_matmul(x, w, cim)
    snap = KERNEL_COUNTERS.snapshot()
    assert "wq" in snap["site_energy"]
    rec = snap["site_energy"]["wq"]
    assert rec["calls"] >= 1 and rec["dots"] >= 2 * 8
    assert rec["energy_j"] > 0
    assert sum(snap["backend_dispatch"].values()) >= 1
    KERNEL_COUNTERS.reset()
    assert not KERNEL_COUNTERS.snapshot()["site_energy"]


def test_accept_buckets_cover_spec_k():
    # integer accept counts land exactly on bucket edges 0..8
    assert ACCEPT_BUCKETS[0] == 0.0 and ACCEPT_BUCKETS[-1] >= 8.0
    assert list(itertools.islice(iter(ACCEPT_BUCKETS), 3)) == [0.0, 1.0, 2.0]


# ---------------------------------------------------------------------------
# spans and the compile counter


class Recorder:
    """Profiler-annotation stand-in that logs each span's enter and exit."""

    log: list = []

    def __init__(self, name, **args):
        self.name, self.args = name, args

    def __enter__(self):
        Recorder.log.append(("enter", self.name, self.args))

    def __exit__(self, *exc):
        Recorder.log.append(("exit", self.name, self.args))


def _small_server(telemetry, **kw):
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    serving = dict(n_slots=2, max_len=32, paged=True, block_size=4,
                   prefill_chunk=4, attn="exact")
    serving.update(kw)
    return Server(params, cfg, ServingConfig(**serving),
                  telemetry=telemetry), cfg


def test_paged_steps_record_five_phases_in_order():
    srv, cfg = _small_server(Telemetry(clock=FakeClock()))
    srv.telemetry.annotation = Recorder
    Recorder.log = []
    rng = np.random.RandomState(2)
    for _ in range(2):
        srv.submit(Request(prompt=rng.randint(0, cfg.vocab, 6).tolist(),
                           max_new_tokens=4))
    srv.run_until_drained()
    snaps = list(srv.telemetry.snapshots)
    assert len(snaps) == srv.metrics.steps
    for s in snaps:
        assert len(s.phases) == len(PHASES)
        assert all(p > 0 for p in s.phases)     # every clock read ticks
        assert sum(s.phases) <= s.wall_s
    # each step opens its phases one after another, in PHASES order,
    # none nested in another; the device span carries the chunk width
    entered = [name for kind, name, _ in Recorder.log
               if kind == "enter" and name != "serve.submit"]
    per_step = [entered[i:i + len(PHASES)]
                for i in range(0, len(entered), len(PHASES))]
    assert len(per_step) == len(snaps)
    assert all(p == [f"serve.{x}" for x in PHASES] for p in per_step)
    depth = 0
    for kind, name, _ in Recorder.log:
        depth += 1 if kind == "enter" else -1
        assert 0 <= depth <= 1, name
    widths = [a["c"] for kind, name, a in Recorder.log
              if kind == "enter" and name == "serve.device"]
    assert widths == [s.c for s in snaps]


def test_build_and_submit_spans_in_summary():
    from repro.core.cim_matmul import CIMConfig

    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32",
                                           cim=CIMConfig(enabled=True))
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    srv = Server(params, cfg, ServingConfig(
        n_slots=2, max_len=32, paged=True, block_size=4, prequant=True),
        telemetry=Telemetry(clock=FakeClock()))
    spans = srv.telemetry.summary()["spans"]
    assert {k: v["count"] for k, v in spans.items()} == \
        {"build": 1, "quantize": 1, "pool": 1}
    # quantize and pool are children of build
    assert spans["quantize"]["seconds"] + spans["pool"]["seconds"] \
        < spans["build"]["seconds"]
    srv.submit(Request(prompt=[1, 2, 3], max_new_tokens=2))
    assert srv.telemetry.summary()["spans"]["submit"]["count"] == 1


def test_phase_spans_in_a_profiler_trace(tmp_path):
    """A few steps under jax.profiler.trace: the host spans
    serve.plan/device/fetch/sample/admit, in order, inside each step."""
    import sys
    from pathlib import Path
    sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "bench"))
    from benchlib import trace as trace_mod

    srv, cfg = _small_server(None)
    for _ in range(2):
        srv.submit(Request(prompt=[5, 6, 7, 8, 9, 10], max_new_tokens=6))
    srv.step()                      # compile outside the trace
    srv.step()
    with jax.profiler.trace(str(tmp_path)):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("test.step"):
                srv.step()
    host = trace_mod.load(str(tmp_path))["host"]
    steps = sorted((s, e) for n, s, e in host if n == "test.step")
    assert len(steps) == 3
    for lo, hi in steps:
        inside = sorted((s, e, n) for n, s, e in host
                        if n.startswith("serve.") and lo <= s and e <= hi)
        assert [n for _, _, n in inside] == [f"serve.{p}" for p in PHASES]
        ends = [e for _, e, _ in inside]
        starts = [s for s, _, _ in inside]
        assert all(e <= s for e, s in zip(ends, starts[1:]))


def test_compile_counter_counts_new_widths_once():
    """The running compile count stamped into each snapshot rises on the
    first step of a new chunk width and stays flat on repeats."""
    srv, cfg = _small_server(None, prefill_chunk=8)
    srv.submit(Request(prompt=list(range(1, 20)), max_new_tokens=6))
    srv.run_until_drained()
    snaps = list(srv.telemetry.snapshots)
    seen, first = set(), []
    for prev, s in zip(snaps, snaps[1:]):
        new = s.c not in seen | {prev.c}
        seen |= {prev.c}
        if new:
            first.append(s)
            assert s.compiles > prev.compiles, (prev, s)
        else:
            assert s.compiles == prev.compiles, (prev, s)
    assert [s.c for s in first] == [1]     # chunk steps first, then decode
    assert snaps[0].compiles > srv.telemetry.compiles_at_start


def test_compile_event_tells_loads_from_compiles():
    k = KernelCounters()
    k.compile_event(BACKEND_COMPILE_EVENT, 2.0, fun_name="f")
    k.compile_event(CACHE_LOAD_EVENT, 0.1)
    k.compile_event(BACKEND_COMPILE_EVENT, 0.3, fun_name="g")
    k.compile_event("/jax/other", 9.0)
    assert (k.compiles, k.compile_s) == (1, 2.0)
    assert (k.cache_loads, k.cache_load_s) == (1, 0.3)
    assert k.compile_events == 2
    assert [kind for _, kind, _ in k.compile_log] == ["compile",
                                                      "cache_load"]
    k.reset()
    assert k.compile_events == 0 and not k.compile_log


def test_soak_chrome_trace_phases_and_compiles(soak):
    srv, _ = soak
    doc = obs.chrome_trace(srv.telemetry)
    assert obs.validate_chrome_trace(doc) == []
    ev = doc["traceEvents"]
    steps = [e for e in ev if e["ph"] == "X" and e["name"].startswith("step")]
    phases = [e for e in ev if e["ph"] == "X"
              and e["name"].startswith("serve.")]
    assert len(phases) == len(PHASES) * len(steps)
    # every phase slice nests inside its step's slice
    for i, st in enumerate(steps):
        for ph in phases[i * len(PHASES):(i + 1) * len(PHASES)]:
            assert st["ts"] <= ph["ts"]
            assert ph["ts"] + ph["dur"] <= st["ts"] + st["dur"] + 1e-3
    snaps = list(srv.telemetry.snapshots)
    compiled = [e for e in ev if e["ph"] == "i" and e["name"] == "compile"]
    assert sum(e["args"]["compiles"] for e in compiled) == \
        snaps[-1].compiles - srv.telemetry.compiles_at_start


def test_soak_prometheus_compile_counters(soak):
    srv, _ = soak
    text = obs.prometheus_text(srv.telemetry, srv)
    k = KERNEL_COUNTERS
    assert f'picoram_compiles_total{{source="backend"}} {k.compiles}' \
        in text
    assert f'picoram_compiles_total{{source="cache"}} {k.cache_loads}' \
        in text
    assert 'picoram_compile_seconds_total{source="backend"}' in text
