"""Paged-KV serving engine: equivalence soaks, chunk invariance, allocator
accounting, prequant composition.

Equivalence contracts (greedy token IDs, exact list equality):

  * paged == one-request-at-a-time decode on ARBITRARY (mixed-depth,
    randomized admission/retirement) schedules — the paged step keeps true
    per-slot positions and per-slot masks, so its math is the single-
    request math regardless of what else shares the batch;
  * paged == the legacy slot engine on DEPTH-ALIGNED schedules (request
    waves admitted and retired together). The legacy engine's shared `pos`
    makes mixed-depth slots attend over zero-K/V gap positions (softmax
    dilution — see the runtime.server module docstring), so it is only an
    exact baseline when all active slots sit at equal depth; the paged
    engine is pinned against it exactly there, and against the one-at-a-
    time reference everywhere.
"""
import dataclasses
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs.registry import SMOKES
from repro.models import registry
from repro.runtime.server import Request, Server, ServingConfig

MAX_LEN = 64

_FORCED = os.environ.get("REPRO_FORCE_JNP", "").strip().lower() in (
    "1", "true", "yes")
needs_pallas = pytest.mark.skipif(
    _FORCED, reason="explicit Pallas attention backend; REPRO_FORCE_JNP "
                    "leg is jnp-only")


@pytest.fixture(scope="module")
def setup():
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_LEN)
    mod = registry.get_module(cfg)
    prefill = jax.jit(lambda p, b: mod.prefill(p, b, cfg, max_len=MAX_LEN))
    decode = jax.jit(lambda p, t, c: mod.decode_step(p, t, c, cfg))

    def one_at_a_time(prompt, n_new, eos_id=None):
        logits, cache = prefill(
            params, {"tokens": jnp.asarray([prompt], jnp.int32)})
        out = [int(jnp.argmax(logits[0]))]
        while len(out) < n_new:
            logits, cache = decode(
                params, jnp.asarray([[out[-1]]], jnp.int32), cache)
            out.append(int(jnp.argmax(logits[0])))
            if eos_id is not None and out[-1] == eos_id:
                break
        return out

    return cfg, params, one_at_a_time


def _mk_server(cfg, params, **kw):
    kw.setdefault("n_slots", 2)
    kw.setdefault("max_len", MAX_LEN)
    kw.setdefault("block_size", 8)
    kw.setdefault("prefill_chunk", 4)
    # the BIT-identity soaks in this file pin the exact attention backend
    # (the dense-cache-equivalent math); the Pallas kernel backend agrees
    # within float tolerance and has its own soak below
    kw.setdefault("attn", "exact")
    return Server(params, cfg, ServingConfig(paged=True, **kw))


# ---------------------------------------------------------------------------
# equivalence: paged vs one-at-a-time on a mixed-depth random schedule
# ---------------------------------------------------------------------------
def test_soak_mixed_depth_vs_single_request(setup):
    """Randomized admission: requests land mid-flight at arbitrary depths
    (the schedule the legacy engine cannot serve exactly); every request's
    tokens must equal its single-request decode."""
    cfg, params, one_at_a_time = setup
    rng = np.random.RandomState(42)
    server = _mk_server(cfg, params)
    schedule = {0: 2, 2: 1, 3: 1, 7: 1}   # step → submissions
    reqs, step = [], 0
    while reqs == [] or any(not r.done for r in reqs) or server.queue:
        for _ in range(schedule.get(step, 0)):
            plen = int(rng.randint(3, 9))
            r = Request(prompt=rng.randint(0, cfg.vocab, size=plen).tolist(),
                        max_new_tokens=int(rng.randint(2, 6)))
            server.submit(r)
            reqs.append(r)
        server.step()
        step += 1
        assert step < 200, "schedule did not drain"
    for r in reqs:
        assert r.output == one_at_a_time(r.prompt, r.max_new_tokens), r.rid
    # pool fully recycled after the drain: only trie-cached prefix blocks
    # remain, and flushing the prefix cache releases those too
    server.flush_prefix_cache()
    assert server.alloc.stats.in_use == 0
    assert server.kv_cache_bytes()["in_use"] == 0


@pytest.mark.slow
def test_soak_waves_vs_legacy_and_single(setup):
    """Seeded admission/retirement soak in depth-aligned waves: all three
    engines — paged, legacy slots, one-at-a-time — produce bit-identical
    token lists. Waves re-admit into freshly freed blocks (LIFO free list),
    so stale block contents from retired requests are constantly reused."""
    cfg, params, one_at_a_time = setup
    rng = np.random.RandomState(3)
    waves = []
    for _ in range(4):
        n = int(rng.randint(1, 3))
        plen = int(rng.randint(3, 10))
        mnew = int(rng.randint(2, 7))
        waves.append([
            Request(prompt=rng.randint(0, cfg.vocab, size=plen).tolist(),
                    max_new_tokens=mnew) for _ in range(n)])

    def run(paged):
        # sharing disabled: this soak pins the RAW allocator lifecycle
        # (every block freed at retirement; reuse = allocs > peak) — the
        # trie's deliberate block retention has its own tests
        srv = _mk_server(cfg, params, prefix_sharing=False) if paged else \
            Server(params, cfg, ServingConfig(n_slots=2, max_len=MAX_LEN))
        outs = []
        for wave in waves:
            ws = [Request(prompt=list(r.prompt),
                          max_new_tokens=r.max_new_tokens) for r in wave]
            for r in ws:
                srv.submit(r)
            srv.run_until_drained()
            outs.extend(r.output for r in ws)
        return outs, srv

    legacy, _ = run(False)
    paged, srv = run(True)
    assert legacy == paged
    singles = [one_at_a_time(r.prompt, r.max_new_tokens)
               for wave in waves for r in wave]
    assert paged == singles
    # the soak actually exercised block reuse, not just first allocation
    st = srv.alloc.stats
    assert st.total_allocs > st.peak_in_use
    assert st.total_frees == st.total_allocs and st.in_use == 0


def test_eos_retirement_paged(setup):
    cfg, params, one_at_a_time = setup
    ref = one_at_a_time([1, 2, 3], 8)
    # the eos id must not occur earlier in the stream, or the request would
    # (rightly) retire at that earlier occurrence
    cut = next(i for i in range(1, len(ref)) if ref[i] not in ref[:i])
    eos = ref[cut]
    server = _mk_server(cfg, params, n_slots=1)
    req = Request(prompt=[1, 2, 3], max_new_tokens=8, eos_id=eos)
    server.submit(req)
    server.run_until_drained()
    assert req.done and len(req.output) == cut + 1
    assert req.output == ref[:cut + 1]


# ---------------------------------------------------------------------------
# chunked prefill: chunk-size invariance through the unified step
# ---------------------------------------------------------------------------
def test_prefill_chunk_size_invariance(setup):
    """The exact-softmax paged prefill makes outputs independent of the
    chunk schedule: 2-token chunks, 5-token chunks and one whole-prompt
    chunk give identical tokens (and match single-request decode)."""
    cfg, params, one_at_a_time = setup
    prompt = [7, 3, 11, 19, 2, 5, 13]
    ref = one_at_a_time(prompt, 5)
    for chunk in (2, 5, 16):
        server = _mk_server(cfg, params, n_slots=1, prefill_chunk=chunk)
        req = Request(prompt=list(prompt), max_new_tokens=5)
        server.submit(req)
        server.run_until_drained()
        assert req.output == ref, f"chunk={chunk}"


def test_token_budget_throttles_prefill(setup):
    """A token budget below the chunk width stalls prefill lanes without
    corrupting results; decode lanes keep priority."""
    cfg, params, one_at_a_time = setup
    server = _mk_server(cfg, params, prefill_chunk=4, token_budget=2)
    rng = np.random.RandomState(1)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=6).tolist(),
                    max_new_tokens=3) for _ in range(3)]
    for r in reqs:
        server.submit(r)
    server.run_until_drained()
    for r in reqs:
        assert r.output == one_at_a_time(r.prompt, 3)
    assert server.metrics.prefill_tokens == sum(len(r.prompt) for r in reqs)


# ---------------------------------------------------------------------------
# capacity accounting + composition + guardrails
# ---------------------------------------------------------------------------
def test_preemption_under_pool_pressure(setup):
    """A pool sized for ~one request: optimistic watermark admission lets
    several lanes in, pool pressure preempts the newest back to the queue,
    and every request still drains bit-identical to the reference — the
    preempted lane resumes its own (prompt + emitted tokens) prefix, and
    greedy decode makes the resume deterministic."""
    cfg, params, one_at_a_time = setup
    # worst case per request below: ceil((8 + 4) / 8) = 2 blocks
    server = _mk_server(cfg, params, num_blocks=3, prefix_sharing=False,
                        n_slots=3)
    rng = np.random.RandomState(5)
    reqs = [Request(prompt=rng.randint(0, cfg.vocab, size=8).tolist(),
                    max_new_tokens=4) for _ in range(3)]
    for r in reqs:
        server.submit(r)
    server.run_until_drained()
    for r in reqs:
        assert r.output == one_at_a_time(r.prompt, 4)
    assert server.metrics.preemptions > 0   # pressure actually hit
    assert server.alloc.stats.peak_in_use <= 3
    assert server.alloc.stats.in_use == 0


def test_kv_bytes_scale_with_occupancy(setup):
    """The paged pool's in-use bytes track allocated blocks, not slots —
    the memory win over the monolithic [n_slots, max_len] cache."""
    cfg, params, _ = setup
    server = _mk_server(cfg, params, n_slots=4)
    legacy = Server(params, cfg, ServingConfig(n_slots=4, max_len=MAX_LEN))
    req = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=4)
    server.submit(req)
    server.step()
    kv = server.kv_cache_bytes()
    assert 0 < kv["in_use"] < kv["total"]
    lv = legacy.kv_cache_bytes()
    assert lv["in_use"] == lv["total"]     # slot cache is always resident
    # one 5-token prompt occupies 1 block = 1/(4 slots × 8 blocks) of parity
    assert kv["in_use"] * 8 < lv["total"]


def test_prequant_packed_paged_matches_legacy():
    """PackedCodes (nibble-packed int4) serving weights compose with the
    paged cache: identical tokens to the legacy prequant engine."""
    from repro.core.cim_matmul import CIMConfig
    cfg = SMOKES["internlm2-1.8b"].replace(dtype="float32",
                                           cim=CIMConfig(enabled=True))
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=MAX_LEN)
    outs = {}
    for paged in (False, True):
        server = Server(params, cfg, ServingConfig(
            n_slots=1, max_len=MAX_LEN, prequant=True, packed=True,
            paged=paged, block_size=8, prefill_chunk=4))
        q = [v for k, v in
             jax.tree_util.tree_flatten_with_path(server.params)[0]
             if str(k[-1]).find("_q") >= 0]
        assert q and all(a.dtype == jnp.uint8 for a in q)
        req = Request(prompt=[5, 9, 2, 7], max_new_tokens=4)
        server.submit(req)
        server.run_until_drained()
        outs[paged] = req.output
    assert outs[True] == outs[False]


def test_request_metrics_recorded(setup):
    cfg, params, _ = setup
    server = _mk_server(cfg, params)
    req = Request(prompt=[1, 2, 3, 4], max_new_tokens=3)
    server.submit(req)
    server.run_until_drained()
    assert req.done
    assert req.t_submit <= req.t_first <= req.t_done
    assert req.latency_s >= req.ttft_s >= 0.0
    m = server.metrics.summary()
    assert m["prefill_tokens"] == 4
    assert m["decode_tokens"] == len(req.output) - 1
    assert m["decode_tok_s"] > 0


def test_eos_on_first_token_retires_at_prefill(setup):
    """An EOS emitted as the very first (prefill-time) token retires the
    request immediately — no post-EOS decoding on a held slot."""
    cfg, params, one_at_a_time = setup
    first = one_at_a_time([1, 2, 3], 1)[0]
    server = _mk_server(cfg, params, n_slots=1)
    req = Request(prompt=[1, 2, 3], max_new_tokens=8, eos_id=first)
    server.submit(req)
    server.run_until_drained()
    assert req.done and req.output == [first]
    server.flush_prefix_cache()
    assert server.alloc.stats.in_use == 0


def test_invalid_scheduler_params_rejected(setup):
    cfg, params, _ = setup
    with pytest.raises(ValueError):
        _mk_server(cfg, params, token_budget=0)
    with pytest.raises(ValueError):
        _mk_server(cfg, params, prefill_chunk=0)


def test_empty_prompt_rejected_both_engines(setup):
    cfg, params, _ = setup
    for srv in (_mk_server(cfg, params),
                Server(params, cfg, ServingConfig(n_slots=1,
                                                  max_len=MAX_LEN))):
        with pytest.raises(ValueError):
            srv.submit(Request(prompt=[], max_new_tokens=2))
        assert srv.queue == [] and not any(srv.slot_req)


def test_decode_lanes_never_exceed_budget(setup):
    """Scheduler invariant: a lane only becomes decode by completing
    prefill, which itself consumes budget, so decode lanes can never
    outnumber token_budget — no decode lane is ever dropped
    (stalled_decodes stays 0; prefill lanes absorb all the stalling), and
    a budget of 1 still drains correctly with single-request-identical
    outputs."""
    cfg, params, one_at_a_time = setup
    server = _mk_server(cfg, params, token_budget=1, prefill_chunk=1)
    reqs = [Request(prompt=[3 + s, 7, 2], max_new_tokens=4)
            for s in range(2)]
    for r in reqs:
        server.submit(r)
    server.run_until_drained(max_steps=500)
    for r in reqs:
        assert r.output == one_at_a_time(r.prompt, 4)
    assert server.metrics.stalled_decodes == 0
    assert server.metrics.stalled_prefills > 0


def test_legacy_metrics_share_one_clock(setup):
    """The slot engine's submit-time prefill counts toward prefill_tokens
    and wall_s, so its tok/s rates are comparable with the paged engine's
    (whose prefill runs inside step())."""
    cfg, params, _ = setup
    server = Server(params, cfg, ServingConfig(n_slots=1, max_len=MAX_LEN))
    req = Request(prompt=[1, 2, 3, 4, 5], max_new_tokens=3)
    server.submit(req)
    server.run_until_drained()
    m = server.metrics.summary()
    assert m["prefill_tokens"] == 5
    assert m["wall_s"] > 0 and m["prefill_tok_s"] > 0


def test_max_new_one_matches_single_request(setup):
    """A max_new_tokens=1 request completes at prefill time with exactly
    one token (one-at-a-time semantics; the legacy engine overshoots to 2
    — documented divergence)."""
    cfg, params, one_at_a_time = setup
    server = _mk_server(cfg, params, n_slots=1)
    req = Request(prompt=[4, 8, 15], max_new_tokens=1)
    server.submit(req)
    server.run_until_drained()
    assert req.done and req.output == one_at_a_time([4, 8, 15], 1)
    server.flush_prefix_cache()
    assert server.alloc.stats.in_use == 0


def test_unservable_requests_rejected_at_submit(setup):
    """Poison requests must be rejected BEFORE queueing: an oversized
    prompt or a worst-case reservation larger than the whole pool would
    otherwise stall admission forever (or raise mid-serve) and strand
    in-flight requests."""
    cfg, params, _ = setup
    server = _mk_server(cfg, params, num_blocks=2)
    good = Request(prompt=[1, 2, 3], max_new_tokens=3)
    server.submit(good)
    with pytest.raises(ValueError):   # needs ceil(36/8)=5 > 2 blocks
        server.submit(Request(prompt=list(range(20)), max_new_tokens=16))
    with pytest.raises(ValueError):   # prompt longer than max_len
        server.submit(Request(prompt=list(range(MAX_LEN)), max_new_tokens=2))
    assert server.queue == []         # nothing poisoned the queue
    server.run_until_drained()        # in-flight request still completes
    assert good.done and len(good.output) == 3


# ---------------------------------------------------------------------------
# Pallas attention-kernel backend: soak parity + trash-block hardening
# ---------------------------------------------------------------------------
@needs_pallas
def test_soak_mixed_depth_kernel_backend(setup):
    """The Pallas flash backend through the full serving loop: randomized
    mixed-depth admission, greedy tokens equal to one-request-at-a-time
    decode (the kernel agrees with exact within float tolerance — far
    below the logit gaps of this seeded schedule)."""
    cfg, params, one_at_a_time = setup
    rng = np.random.RandomState(9)
    server = _mk_server(cfg, params, attn="kernel")
    schedule = {0: 2, 3: 1}
    reqs, step = [], 0
    while reqs == [] or any(not r.done for r in reqs) or server.queue:
        for _ in range(schedule.get(step, 0)):
            plen = int(rng.randint(3, 9))
            r = Request(prompt=rng.randint(0, cfg.vocab, size=plen).tolist(),
                        max_new_tokens=int(rng.randint(2, 5)))
            server.submit(r)
            reqs.append(r)
        server.step()
        step += 1
        assert step < 200, "schedule did not drain"
    for r in reqs:
        assert r.output == one_at_a_time(r.prompt, r.max_new_tokens), r.rid
    server.flush_prefix_cache()
    assert server.alloc.stats.in_use == 0


@needs_pallas
def test_prefill_chunk_invariance_kernel_backend(setup):
    """Chunk-size invariance holds on the kernel backend too: the online
    softmax accumulates over KV blocks, not prompt chunks, so the chunk
    schedule cannot reassociate the reduction."""
    cfg, params, one_at_a_time = setup
    prompt = [7, 3, 11, 19, 2, 5, 13]
    ref = one_at_a_time(prompt, 4)
    for chunk in (2, 5, 16):
        server = _mk_server(cfg, params, n_slots=1, prefill_chunk=chunk,
                            attn="kernel")
        req = Request(prompt=list(prompt), max_new_tokens=4)
        server.submit(req)
        server.run_until_drained()
        assert req.output == ref, f"chunk={chunk}"


def _poison_trash_block(server, value):
    """Fill physical block 0 of every layer pool with `value`."""
    server.cache = jax.tree.map(lambda a: a.at[:, 0].set(value),
                                server.cache)


@pytest.mark.parametrize("attn", ["exact",
                                  pytest.param("kernel",
                                               marks=needs_pallas)])
@pytest.mark.parametrize("poison", [float("nan"), 1e6])
def test_trash_block_poison_server(setup, attn, poison):
    """Poison physical block 0 (the masked-lane write sink / unallocated-
    table target) with NaN / huge garbage before serving: a mixed-depth
    schedule must produce exactly the tokens of a clean run on BOTH
    attention backends — any future softmax-weight leak onto the trash
    block shows up here immediately."""
    cfg, params, _ = setup
    rng = np.random.RandomState(17)
    prompts = [rng.randint(0, cfg.vocab, size=int(rng.randint(3, 9))).tolist()
               for _ in range(3)]

    def drain(poison_value):
        server = _mk_server(cfg, params, attn=attn)
        if poison_value is not None:
            _poison_trash_block(server, poison_value)
        reqs = [Request(prompt=list(p), max_new_tokens=3) for p in prompts]
        for r in reqs:
            server.submit(r)
        server.run_until_drained()
        return [r.output for r in reqs]

    assert drain(poison) == drain(None)


def test_unsupported_arch_raises():
    """MLA latent caches (deepseek) keep the dense slot engine for now —
    requesting paged serving must fail loudly, not silently fall back."""
    cfg = SMOKES["deepseek-v3-671b"].replace(dtype="float32")
    params = registry.init_params(jax.random.PRNGKey(0), cfg, max_seq=32)
    with pytest.raises(NotImplementedError):
        Server(params, cfg, ServingConfig(n_slots=1, max_len=32, paged=True,
                                          block_size=8))


# ---------------------------------------------------------------------------
# mesh-sharded serving (subprocess: 2 forced host devices, `--mesh host`)
# ---------------------------------------------------------------------------
MESH_SCRIPT = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=2"
os.environ.pop("REPRO_FORCE_JNP", None)
import jax
from repro.launch import serve
from repro.models import registry
from repro.parallel import sharding

flags = ["--smoke", "--paged", "--attn", "kernel", "--cim", "bp-prequant",
         "--slots", "4", "--requests", "4", "--max-new", "6",
         "--max-len", "64"]
args = serve.build_parser().parse_args(flags)
params = registry.init_params(jax.random.PRNGKey(0), serve.model_config(args),
                              max_seq=args.max_len)
streams = {}
for mesh_flag in ("none", "host"):
    args = serve.build_parser().parse_args(flags + ["--mesh", mesh_flag])
    mesh = serve.install_mesh(args)
    try:
        server = serve.build_server(args, params=params)
        reqs = serve.synthetic_requests(args, server.cfg.vocab)
        for r in reqs:
            server.submit(r)
        server.run_until_drained()
    finally:
        sharding.set_mesh(None)
    streams[mesh_flag] = [r.output for r in reqs]
    assert all(r.done and len(r.output) == 6 for r in reqs)
    leaves = jax.tree_util.tree_leaves(server.params)
    split = sum(not a.sharding.is_fully_replicated for a in leaves)
    pools = jax.tree_util.tree_leaves(server.cache)
    if mesh_flag == "host":
        # the parameters and the KV pools (KV heads over "model") are laid
        # out over the mesh, not left on the device they started on
        assert {d for a in leaves for d in a.sharding.device_set} \
            == set(jax.devices())
        assert split > 0
        assert all(len(p.sharding.device_set) == 2
                   and not p.sharding.is_fully_replicated for p in pools)
    else:
        assert split == 0
assert streams["none"] == streams["host"], streams
print("MESH_SERVING_OK")
"""


def test_mesh_sharded_serving_matches_unsharded():
    """`serve.py --mesh host` on 2 devices (data 1 × model 2, which divides
    the smoke model's 2 KV heads): the params and KV pools are sharded
    across the devices, and the greedy streams equal the unsharded
    server's on the same weights."""
    import subprocess
    import sys
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(root, "src")
    env.pop("XLA_FLAGS", None)
    proc = subprocess.run([sys.executable, "-c", MESH_SCRIPT], env=env,
                          capture_output=True, text=True, timeout=600)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_SERVING_OK" in proc.stdout
