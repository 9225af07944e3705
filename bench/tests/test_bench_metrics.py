"""Each metric reader on a run whose numbers are known."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import harness, reference, spec  # noqa: E402

BM = spec.benchmark()


def read(name, ctx):
    return spec.metric_reader(name).read(ctx)


def ctx(**kw):
    m = reference.Model(spec.config(BM, "internlm2-1.8b"))
    lens = np.full(32, 100)
    dec = harness.Call(0.050, 1, lens, np.ones(32, np.int64))
    pre = harness.Call(0.200, 16, lens, np.full(32, 16))
    steps = [harness.Step(-1.0, -0.9, [pre]),           # set-up, not counted
             harness.Step(0.0, 0.060, [dec]),
             harness.Step(0.060, 0.120, [dec]),
             harness.Step(0.120, 0.330, [pre]),
             harness.Step(0.330, 0.400, [dec])]
    base = dict(setup_s=42.0, t_w0=0.0, t_w1=0.4, window_s=0.4,
                tokens=200, itl=[0.06] * 19 + [0.27], steps=steps,
                traced_steps=steps[1:3], trace=None, model=m,
                peaks=spec.peaks("TPU v5 lite"),
                kernels=spec.kernel_models(), param_init_s=3.0,
                server_build_s=20.0)
    base.update(kw)
    return harness.Ctx(**base)


def test_end_to_end_readers():
    c = ctx()
    assert read("tok_s", c) == pytest.approx(500.0)
    assert read("setup_s", c) == 42.0
    # 20 gaps, linear interpolation: 95th percentile at rank 18.05
    assert read("itl_p95_ms", c) == pytest.approx(
        1e3 * np.percentile([0.06] * 19 + [0.27], 95))
    assert read("itl_p95_ms", ctx(itl=[])) is None


def test_step_readers():
    c = ctx()
    assert read("decode_step_ms", c) == pytest.approx(50.0)
    assert read("prefill_step_ms", c) == pytest.approx(200.0)
    # step walls 60, 60, 210, 70 ms less 50, 50, 200, 50 ms of device call
    assert read("host_ms_per_step", c) == pytest.approx(12.5)
    assert read("param_init_s", c) == 3.0
    assert read("server_build_s", c) == 20.0


def test_trace_readers_need_a_trace():
    c = ctx()
    for name in ("device_idle_share", "step_mfu", "cim_mvm_roofline",
                 "paged_attn_roofline"):
        assert read(name, c) is None


def test_trace_readers():
    t = {"window_s": 0.12, "busy_s": 0.09,
         "kernel_s": {"cim_mvm": 0.06, "paged_attn": 0.02}}
    c = ctx(trace=t)
    assert read("device_idle_share", c) == pytest.approx(25.0)
    m = c.model
    peaks = c.peaks
    # two decode steps of 32 lanes, 100 cached tokens each
    cim = sum(max(f / peaks["int8_ops"], b / peaks["hbm_bytes_s"])
              for f, b in spec.kernel_models()["cim_mvm"].calls(
                  m, 1, np.full(32, 100), np.ones(32))) * 2
    assert read("cim_mvm_roofline", c) == pytest.approx(100 * cim / 0.06)
    attn = spec.kernel_models()["paged_attn"].calls(
        m, 1, np.full(32, 100), np.ones(32))
    least = sum(max(f / peaks["bf16_flops"], b / peaks["hbm_bytes_s"])
                for f, b in attn) * 2
    assert read("paged_attn_roofline", c) == pytest.approx(100 * least / 0.02)
    flops = 2 * (2 * (m.matmul_params() - m.d * m.vocab) * 32
                 + 2 * m.d * m.vocab * 32
                 + 4 * m.heads * m.dh * 32 * 101 * m.layers)
    assert read("step_mfu", c) == pytest.approx(
        100 * flops / (0.12 * peaks["int8_ops"]))
    assert 0 < read("step_mfu", c) < 100


class Req:
    def __init__(self, n):
        self.prompt = [0] * n


def test_prompt_tokens_count_each_position_once():
    a, b = Req(40), Req(20)
    lanes = lambda lens, valid, occ: harness.Call(  # noqa: E731
        0.1, 16, np.array(lens), np.array(valid), occ)
    steps = [
        # set-up: a's first chunk, not counted
        harness.Step(-1.0, -0.9, [lanes([0, 0], [16, 0], (a, None))]),
        # a's second chunk; b admitted on 16 prefix-cache hits
        harness.Step(0.0, 0.1, [lanes([16, 16], [16, 4], (a, b))]),
        # b decodes, a finishes its prompt (8 positions)
        harness.Step(0.1, 0.2, [lanes([32, 20], [8, 1], (a, b))]),
        # a preempted and resumed: its prompt computed again from 0
        harness.Step(0.2, 0.3, [lanes([0, 21], [16, 1], (a, b))]),
    ]
    assert harness.prompt_tokens(steps, 0.0) == 16 + 20 + 8


def test_chunked_positions_follow_the_last_call():
    """Positions served in a step of chunks (C > 1) are flagged; a
    position computed again in a one-token step loses its flag."""
    a, b = Req(20), Req(3)
    call = lambda c, lens, valid, occ: harness.Call(  # noqa: E731
        0.1, c, np.array(lens), np.array(valid), occ)
    calls = [call(16, [0, 0], [16, 3], (a, b)),
             call(16, [16, 3], [4, 1], (a, b)),
             call(1, [20, 4], [1, 1], (a, b)),
             call(1, [0, 5], [0, 1], (None, b)),
             call(1, [0, 2], [0, 1], (None, b))]
    wide = harness.chunked_positions(calls)
    assert wide[id(a)] == set(range(20))
    assert wide[id(b)] == {0, 1, 3}


def test_chunk_norm_is_the_norm():
    """The chunk-shaped RMSNorm is the same arithmetic as the row one; on
    the CPU both round alike."""
    m = reference.Model(spec.config(BM, "internlm2-1.8b"))
    x = (np.random.default_rng(0).standard_normal((32, 256)) * 0.02)
    x = reference.jnp.asarray(x, reference.jnp.bfloat16)
    scale = reference.jnp.ones((256,), reference.jnp.bfloat16)
    flat = reference.rmsnorm(x, scale, m)
    mask = np.arange(32) % 3 == 0
    mixed = reference.rmsnorm(x, scale, m, mask)
    assert np.array_equal(np.asarray(flat, np.float32),
                          np.asarray(mixed, np.float32))


def test_emitted_tokens_inside_the_window():
    r = harness.Record(item=None, req=None, t_submit=0.0,
                       emits=[-0.1, 0.1, 0.2, 0.5])
    assert harness.emitted_tokens([r], 0.0, 0.4) == 2


def test_kv_live_bytes_counts_held_blocks():
    m = reference.Model(spec.config(BM, "internlm2-1.8b"))
    call = harness.Call(0.1, 1, np.array([15, 100, 7]),
                        np.array([1, 1, 0]), (Req(1), Req(1), None))
    # 16 and 101 tokens: 1 + 7 blocks of 16; the free lane holds none
    assert harness.kv_live_bytes(call, m, 16) == 8 * 16 * 98_304


def test_cim_least_work_leaves_out_padded_rows():
    """A prefill step of width 16 in which one lane takes a 5-token chunk
    and three decode: 8 useful rows, not 4·16."""
    m = reference.Model(spec.config(BM, "internlm2-1.8b"))
    k = spec.kernel_models()["cim_mvm"]
    calls = k.calls(m, 16, np.array([0, 50, 60, 70]), np.array([5, 1, 1, 1]))
    assert calls[0][0] == 2 * 8 * 2048 * 2048
    assert calls[-1][0] == 2 * 4 * 2048 * 92544
