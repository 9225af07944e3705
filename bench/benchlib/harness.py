"""One run of one cell: set-up, the measured window, the optional trace,
the comparison with the reference, and the metrics.

The system under test is the program's serving main path, built the way
its launcher builds it: `launch.serve.build_parser` flags →
`serve.model_config` → `ServingConfig.from_flags` → `Server`, driven
through `Server.submit` / `Server.step`. The benchmark supplies the
weights (bench/benchlib/weights.py) and the static activation grid (its
own calibration), and wraps the server's jitted step to time each device
call. Spans of its own (`bench.*` TraceAnnotations) mark the host's work
in a trace.
"""
from __future__ import annotations

import dataclasses
import gc
import os
import sys
import time
from pathlib import Path
from typing import NamedTuple

import numpy as np

from . import reference, spec, traffic, weights
from . import trace as trace_mod

TRACE_SECONDS = 2.0     # device trace: at least this long, in whole steps
TRACE_MIN_STEPS = 3


class NoChip(RuntimeError):
    """JAX finds no accelerator, or fewer chips than the cell asks for."""


@dataclasses.dataclass
class Record:
    item: traffic.Item
    req: object
    t_submit: float
    emits: list = dataclasses.field(default_factory=list)
    t_done: float | None = None


class Call(NamedTuple):
    """One jitted step call: its wall time until the results were ready,
    its chunk width, each lane's cached length and new tokens, the request
    in each lane (None for a free one), and the device's bytes in use once
    its results were ready."""
    wall: float
    c: int
    lens: np.ndarray
    valid: np.ndarray
    occupants: tuple = ()
    bytes_in_use: int = 0


@dataclasses.dataclass
class Step:
    t0: float
    t1: float
    calls: list          # [Call] device calls inside


class Ctx:
    """What a metric reader sees of one run."""

    def __init__(self, **kw):
        self.__dict__.update(kw)

    def window_steps(self):
        return [s for s in self.steps if s.t0 >= self.t_w0]

    def calls(self, c=None):
        return [call for s in self.window_steps() for call in s.calls
                if c is None or call.c == c]

    def kernel_roofline(self, name: str):
        """% of the kernel's device time in the traced window that the
        least time its calls need would take; None where no event of the
        kernel was found."""
        t = self.trace
        if t is None or not t["kernel_s"].get(name):
            return None
        mod = self.kernels[name]
        peak = self.peaks[mod.PEAK]
        bw = self.peaks["hbm_bytes_s"]
        least = 0.0
        for s in self.traced_steps:
            for call in s.calls:
                for flops, nbytes in mod.calls(self.model, call.c,
                                               call.lens, call.valid):
                    least += max(flops / peak, nbytes / bw)
        return 100.0 * least / t["kernel_s"][name]


def _configure_jax(cache_dir):
    import jax
    if cache_dir is not None:
        jax.config.update("jax_compilation_cache_dir", str(cache_dir))
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return jax


def _devices(jax, chips: int, require_chip: bool):
    devs = jax.devices()
    if require_chip and (devs[0].platform != "tpu" or len(devs) < chips):
        raise NoChip(f"the cell needs {chips} TPU chip(s); JAX finds "
                     f"{len(devs)} {devs[0].platform} device(s)")
    return devs[:chips]


def build_server(conf: dict, seed: int, root: Path):
    """The program's server for this configuration, with the benchmark's
    weights and grid. Returns (server, make_weights, words, grid, times)."""
    import jax
    if str(root / "src") not in sys.path:
        sys.path.insert(0, str(root / "src"))
    from repro.launch import serve
    from repro.models import registry
    from repro.runtime.server import Server, ServingConfig

    args = serve.build_parser().parse_args(conf["serving"]["flags"])
    cfg = serve.model_config(args)
    cfg = cfg.replace(**conf["program"].get("overrides", {}))
    m = reference.Model(conf)
    mismatch = {k: (a, b) for k, a, b in (
        ("d_model", cfg.d_model, m.d), ("n_heads", cfg.n_heads, m.heads),
        ("n_kv_heads", cfg.n_kv_heads, m.kv_heads),
        ("head_dim", cfg.head_dim, m.dh), ("d_ff", cfg.d_ff, m.d_ff),
        ("vocab", cfg.vocab, m.vocab), ("n_layers", cfg.n_layers, m.layers),
        ("tie_embeddings", cfg.tie_embeddings, m.tied)) if a != b}
    if mismatch:
        raise ValueError(f"program config departs from the file: {mismatch}")
    abstract = jax.eval_shape(lambda: registry.init_params(
        jax.random.PRNGKey(0), cfg, max_seq=args.max_len))
    make = weights.maker(abstract, cfg.n_layers)
    words = weights.seed_words(seed)
    times = {}
    t0 = time.perf_counter()
    params = jax.block_until_ready(make(words))
    times["param_init_s"] = time.perf_counter() - t0

    rng = np.random.default_rng([seed, 4])
    cal = rng.integers(0, m.vocab, size=conf["calibration_tokens"])
    lo, hi = reference.act_ranges(params, np.asarray(cal, np.int32), m=m)
    grid = reference.static_grid(float(lo), float(hi), m)

    serving = ServingConfig.from_flags(args, act_scale=grid[0],
                                       act_zero_point=grid[1])
    t0 = time.perf_counter()
    server = Server(params, cfg, serving)
    del params
    jax.block_until_ready((server.params, server.cache))
    times["server_build_s"] = time.perf_counter() - t0
    return server, make, words, grid, m, times


def _warm(server):
    """Compile the step for every chunk width the window uses, on dummy
    inputs whose writes land in the trash block; the result is dropped."""
    import jax
    import jax.numpy as jnp
    b, mb = server.tables.tables.shape
    for c in sorted({1, server.prefill_chunk}):
        z = jnp.zeros((b,), jnp.int32)
        out = server._pstep(server.params, jnp.zeros((b, c), jnp.int32),
                            server.cache, jnp.zeros((b, mb), jnp.int32), z, z)
        jax.block_until_ready(out)
        del out


def _bytes_in_use(devs) -> int:
    return max((d.memory_stats() or {}).get("bytes_in_use", 0) for d in devs)


def _wrap_step(server, steps_calls: list, devs):
    """Time every device call of the server's step: the jitted call until
    its results are ready (the step syncs on the logits right after). Also
    note which request each lane holds, and the bytes then in use."""
    import jax
    for name in ("_pstep", "_pstep_all"):
        fn = getattr(server, name)

        def timed(*a, _fn=fn):
            occupants = tuple(server.slot_req)
            with jax.profiler.TraceAnnotation("bench.device_call"):
                t0 = time.perf_counter()
                out = jax.block_until_ready(_fn(*a))
                wall = time.perf_counter() - t0
            steps_calls.append(Call(wall, int(a[1].shape[1]),
                                    np.asarray(a[4]), np.asarray(a[5]),
                                    occupants, _bytes_in_use(devs)))
            return out
        setattr(server, name, timed)


class ClosedLoop:
    """`concurrency` clients, each sending its next request as soon as the
    previous one finished."""

    def __init__(self, server, plan: traffic.Plan, mix: dict):
        from repro.runtime.server import Request
        self.Request = Request
        self.server = server
        self.plan = plan
        self.records: list[Record] = []
        self.live: list[Record] = []
        self.wave = plan.first_wave(int(mix["concurrency"]))
        self.calls: list = []
        self.steps: list[Step] = []

    def submit(self, item: traffic.Item, now: float):
        import jax
        req = self.Request(prompt=item.prompt, max_new_tokens=item.max_new)
        with jax.profiler.TraceAnnotation("bench.submit"):
            self.server.submit(req)
        rec = Record(item, req, now)
        self.records.append(rec)
        self.live.append(rec)

    def step(self):
        import jax
        n0 = len(self.calls)
        t0 = time.perf_counter()
        with jax.profiler.TraceAnnotation("bench.step"):
            self.server.step()
        t1 = time.perf_counter()
        self.steps.append(Step(t0, t1, self.calls[n0:]))
        done = []
        for rec in self.live:
            n = len(rec.req.output)
            if n > len(rec.emits):
                rec.emits.extend([t1] * (n - len(rec.emits)))
            if rec.req.done:
                rec.t_done = t1
                done.append(rec)
        for rec in done:
            self.live.remove(rec)
        return t1, len(done)

    def setup(self):
        """Prefill the first wave's output-phase requests."""
        now = time.perf_counter()
        for item in self.wave:
            if item.in_setup:
                self.submit(item, now)
        while any(not r.req.output for r in self.live):
            self.step()

    def open_window(self):
        now = time.perf_counter()
        for item in self.wave:
            if not item.in_setup:
                self.submit(item, now)
        for _ in range(len(self.wave) - len(self.live)):
            self.submit(self.plan.next_request(), now)

    def after_step(self, now: float, n_done: int):
        for _ in range(n_done):
            self.submit(self.plan.next_request(), now)


def prompt_tokens(steps, t_w0: float) -> int:
    """Prompt positions of the benchmark's requests first covered by a
    device call inside the window. A lane with `lens` cached tokens and
    `valid` new ones covers its request's prompt up to min(lens + valid,
    prompt length); positions a request skipped (prefix-cache hits) count
    when it passes them, and positions computed again (after a preemption)
    count once."""
    reached, n = {}, 0
    for s in steps:
        for call in s.calls:
            for req, pos, v in zip(call.occupants, call.lens, call.valid):
                if req is None or v <= 0:
                    continue
                end = min(int(pos) + int(v), len(req.prompt))
                prev = reached.get(id(req), 0)
                if end > prev:
                    reached[id(req)] = end
                    if s.t0 >= t_w0:
                        n += end - prev
    return n


def emitted_tokens(records, t_w0: float, t_w1: float) -> int:
    return sum(1 for r in records for t in r.emits if t_w0 < t <= t_w1)


def kv_live_bytes(call: Call, m, block: int) -> int:
    """Bytes of the KV blocks the lanes hold once the call has written."""
    per_token = m.layers * 2 * m.kv_heads * m.dh * m.dtype.itemsize
    blocks = sum(-(-(int(n) + int(v)) // block)
                 for n, v, req in zip(call.lens, call.valid, call.occupants)
                 if req is not None)
    return blocks * block * per_token


def chunked_positions(calls) -> dict:
    """Per request (by id), the positions last computed in a step of
    chunks (C > 1) rather than a one-token decode step."""
    out: dict = {}
    for call in calls:
        for req, pos, v in zip(call.occupants, call.lens, call.valid):
            if req is None or v <= 0:
                continue
            flags = out.setdefault(id(req), {})
            for p in range(int(pos), int(pos) + int(v)):
                flags[p] = call.c > 1
    return {k: {p for p, wide in f.items() if wide} for k, f in out.items()}


def _sample(records, seed: int, conf: dict, calls=()):
    """Finished requests to compare: the one with the most tokens, then
    others drawn from the seed until enough served tokens are covered.
    Each comes with the positions served in steps of chunks."""
    done = [r for r in records if r.req.done]
    if not done:
        return []
    longest = max(done, key=lambda r: len(r.item.prompt) + len(r.req.output))
    rest = [r for r in done if r is not longest]
    order = np.random.default_rng([seed, 5]).permutation(len(rest))
    pick, tokens = [longest], len(longest.req.output)
    want = conf["correct"]["served_tokens"]
    for i in order:
        if tokens >= want or len(pick) >= conf["correct"]["max_requests"]:
            break
        pick.append(rest[i])
        tokens += len(rest[i].req.output)
    wide = chunked_positions(calls)
    return [(list(r.item.prompt), list(r.req.output),
             wide.get(id(r.req), set())) for r in pick]


def compare(samples, params, grid, m, max_len: int, control: bool = False):
    """Teacher-forced reference over each sample's prompt and served
    tokens. Returns the widest gap by which a compared token's logit lies
    below the reference's best, the number of tokens compared, and how many
    of them were not the reference's first choice.

    The tokens compared are the served ones; with `control`, the control's
    in their place: at each served position, the token that the reference
    with float8 keys and values puts first. Each sample is (prompt, served
    tokens, positions served in steps of chunks)."""
    import jax.numpy as jnp
    grid = jnp.asarray(grid, jnp.float32)
    worst, n_tok, n_off = 0.0, 0, 0
    for prompt, out, wide in samples:
        seq = prompt + out[:-1]
        t = len(seq)
        tp = min(max_len, -(-t // 512) * 512)
        chunked = np.zeros(tp, bool)
        chunked[[p for p in wide if p < tp]] = True
        toks = jnp.asarray(np.pad(np.asarray(seq, np.int32), (0, tp - t)))
        tg = np.full(tp, -1, np.int32)
        at = np.arange(len(prompt) - 1, len(prompt) - 1 + len(out))
        tg[at] = out
        if control:
            _, _, arg = reference.stats(params, toks, tg, grid, m=m,
                                        kv_dtype=jnp.float8_e4m3fn,
                                        chunked=chunked)
            tg[at] = arg[at]
        mx, picked, _ = reference.stats(params, toks, tg, grid, m=m,
                                        chunked=chunked)
        gap = mx[at] - picked[at]
        worst = max(worst, float(gap.max()))
        n_off += int((gap > 0).sum())
        n_tok += len(out)
    return worst, n_tok, n_off


def itl_gaps(records, t_w0: float, t_w1: float) -> list:
    gaps = []
    for r in records:
        e = r.emits
        for a, b in zip(e, e[1:]):
            if a >= t_w0 and b <= t_w1:
                gaps.append(b - a)
    return gaps


def run_cell(conf: dict, mix: dict, *, seed: int, seconds: float,
             trace: bool, started: float, chips: int = 1,
             require_chip: bool = True, root: Path = spec.ROOT,
             cache_dir: Path | None = None, out_dir: Path | None = None,
             control: bool = False, on_server=None) -> dict:
    """Run the cell once; return what the result line and the metric
    readers need (see run.py)."""
    os.environ.pop("REPRO_TUNE_CACHE", None)      # default kernel tiles
    if os.environ.get("REPRO_FORCE_JNP", "").strip():
        raise NoChip("REPRO_FORCE_JNP swaps every kernel for jnp")
    jax = _configure_jax(cache_dir)
    devs = _devices(jax, chips, require_chip)
    peaks = spec.peaks(devs[0].device_kind) if require_chip else None

    server, make, words, grid, m, times = build_server(conf, seed, root)
    _warm(server)
    if on_server is not None:
        on_server(server)
    if mix["loop"] != "closed":
        raise ValueError(f"unknown loop kind {mix['loop']!r}")
    plan = traffic.Plan(mix, seed, m.vocab, server.prefill_chunk)
    loop = ClosedLoop(server, plan, mix)
    _wrap_step(server, loop.calls, devs)
    loop.setup()
    setup_peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                     for d in devs)

    # ---- the measured window ----------------------------------------------
    t_w0 = time.perf_counter()
    loop.open_window()
    deadline = t_w0 + seconds
    traced, tracing, t_trace = [], False, None
    win = None
    while True:
        if trace and not tracing and not traced \
                and time.perf_counter() >= t_w0 + seconds / 2:
            # host spans only from TraceAnnotation (level 1); no Python
            # call tracing, which costs the host far more than it shows
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            opts.host_tracer_level = 1
            jax.profiler.start_trace(str(out_dir / "trace"),
                                     profiler_options=opts)
            win = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
            win.__enter__()
            tracing, t_trace = True, time.perf_counter()
        now, n_done = loop.step()
        if tracing:
            traced.append(loop.steps[-1])
            if len(traced) >= TRACE_MIN_STEPS \
                    and now - t_trace >= TRACE_SECONDS:
                win.__exit__(None, None, None)
                jax.profiler.stop_trace()
                tracing = False
        loop.after_step(now, n_done)
        if now >= deadline and not tracing:
            break
    t_w1 = loop.steps[-1].t1
    records, steps = loop.records, loop.steps
    tokens = prompt_tokens(steps, t_w0) \
        + emitted_tokens(records, t_w0, t_w1)

    window_calls = [c for s in steps if s.t0 >= t_w0 for c in s.calls]
    memory = {
        "setup_peak_bytes": setup_peak,
        "window_peak_bytes": max((c.bytes_in_use for c in window_calls),
                                 default=0),
        "kv_pool_bytes": sum(x.nbytes for x in
                             jax.tree_util.tree_leaves(server.cache)),
        "kv_live_peak_bytes": max((kv_live_bytes(c, m, server.block_size)
                                   for c in window_calls), default=0)}
    attempted = sum(1 for r in records
                    if r.t_submit <= t_w1 and (r.t_done is None
                                               or r.t_done >= t_w0))
    in_vocab = all(0 <= t < m.vocab for r in records for t in r.req.output)
    samples = _sample(records, seed, conf, loop.calls)
    max_len = server.max_len
    del server, loop
    gc.collect()

    t_ref = time.perf_counter()
    params = reference.quantize(make(words), m)
    gap, n_tok, n_off = compare(samples, params, grid, m, max_len, control)
    del params
    ref_s = time.perf_counter() - t_ref

    kernels = spec.kernel_models()
    reduced = None
    if trace:
        reduced = trace_mod.reduce(trace_mod.load(str(out_dir / "trace")),
                                   {k: v.PATTERN for k, v in kernels.items()})
    limit = conf["correct"]["logit_gap_max"]
    min_tokens = conf["correct"]["min_tokens"]
    compared = {"logit_gap": {"value": gap, "limit": limit},
                "tokens_compared": {"value": n_tok, "limit": min_tokens}}
    correct = bool(in_vocab and tokens > 0 and n_tok >= min_tokens
                   and gap <= limit)
    ctx = Ctx(setup_s=t_w0 - started, t_w0=t_w0, t_w1=t_w1,
              window_s=t_w1 - t_w0, tokens=tokens,
              itl=itl_gaps(records, t_w0, t_w1), steps=steps,
              traced_steps=traced, trace=reduced, model=m, peaks=peaks,
              kernels=kernels, **times)
    return {"ctx": ctx, "correct": correct, "attempted": attempted,
            "failed": 0, "compared": compared, "tokens_not_first": n_off,
            "reference_s": ref_s, "memory": memory,
            "memory_peak_bytes": memory["window_peak_bytes"],
            "device": {"platform": devs[0].platform,
                       "kind": devs[0].device_kind, "count": len(devs)},
            "grid": grid}
