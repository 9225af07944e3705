"""Trace reduction: busy and idle device time inside the window, device
time per kernel, the top ops and the longest idle gaps by host span."""
import json
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import spec, trace  # noqa: E402

DATA = Path(__file__).resolve().parents[1] / "testdata"
KERNELS = {k: v.PATTERN for k, v in spec.kernel_models().items()}


def small():
    """Window 0–1000 ns; on one device, inside a layer loop 90–820: a CIM
    kernel 100–300, an attention kernel 250–400 (overlapping it), a fusion
    700–800; and one op crossing the window's end, 950–1100."""
    dev = [["%while.7 = (s32[]) while(...)", 90, 820, "while"],
           ["%fusion.1 = bf16[8] fusion(...)", -50, 20, "elementwise"],
           ["%cim_mvm_grouped.83 = f32[512,8192] custom-call(...)", 100, 300,
            'custom_call_target="tpu_custom_call"'],
           ["%_paged_attn_call.11 = f32[32,8,32,128] custom-call(...)", 250,
            400, 'custom_call_target="tpu_custom_call"'],
           ["%fusion.2.clone = f32[8] fusion(...)", 700, 800, "reduce"],
           ["%copy.1 = bf16[8] copy(...)", 950, 1100, "copy"]]
    host = [[trace.WINDOW_SPAN, 0, 1000],
            ["bench.step", 0, 900],
            ["bench.device_call", 90, 410],
            ["bench.submit", 450, 650],
            ["PjitFunction(step)", 440, 660],
            ["bench.step", 900, 1000]]
    return {"device": {"/device:TPU:0": dev}, "host": host}


def test_op_names_drop_text_and_numbers():
    assert trace.op_name("%cim_mvm_grouped.83 = f32[1] custom-call()") == \
        "cim_mvm_grouped"
    assert trace.op_name("%pad.113.clone") == "pad"
    assert trace.op_name("copy-start") == "copy-start"


def test_busy_is_the_union_inside_the_window():
    r = trace.reduce(small(), KERNELS)
    assert r["window_s"] == pytest.approx(1000e-9)
    # 0–20, 100–400, 700–800, 950–1000
    assert r["busy_s"] == pytest.approx(470e-9)


def test_kernel_time_is_matched_by_name():
    r = trace.reduce(small(), KERNELS)
    assert r["kernel_s"]["cim_mvm"] == pytest.approx(200e-9)
    assert r["kernel_s"]["paged_attn"] == pytest.approx(150e-9)
    assert r["kernel_calls"] == {"cim_mvm": 1, "paged_attn": 1}


def test_top_ops_and_gaps_by_host_span():
    r = trace.reduce(small(), KERNELS)
    ops = dict(r["device_ops"])
    assert [n for n, _ in r["device_ops"]][:2] == ["cim_mvm_grouped",
                                                    "_paged_attn_call"]
    assert ops["fusion"] == pytest.approx(120e-9)
    assert "while" not in ops
    gaps = r["idle_gaps"]
    # 400–700 (mid 550: inside bench.submit, not the program's own span),
    # 20–100, 800–950
    assert gaps[0] == ["bench.submit", pytest.approx(300e-9)]
    assert {g[0] for g in gaps} == {"bench.submit", "bench.step"}
    assert sum(s for _, s in gaps) == pytest.approx(530e-9)


def test_no_window_span_is_an_error():
    t = small()
    t["host"] = [h for h in t["host"] if h[0] != trace.WINDOW_SPAN]
    with pytest.raises(ValueError):
        trace.reduce(t, KERNELS)


def test_recorded_chip_trace():
    """A few steps of a chip trace, reduced to the plain events
    `trace.load` returns (bench/testdata/recorded_trace.json)."""
    with open(DATA / "recorded_trace.json") as f:
        rec = json.load(f)
    r = trace.reduce(rec["trace"], KERNELS)
    want = rec["reduced"]
    assert r["window_s"] == pytest.approx(want["window_s"])
    assert r["busy_s"] == pytest.approx(want["busy_s"])
    for k, v in want["kernel_s"].items():
        assert r["kernel_s"][k] == pytest.approx(v)
    assert 0 < r["busy_s"] <= r["window_s"]
    assert set(r["kernel_s"]) == {"cim_mvm", "paged_attn"}
