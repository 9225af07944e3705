"""The ops and bytes the kernels' rooflines count, against numbers
reckoned by hand."""
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import reference, spec  # noqa: E402


def model(name):
    return reference.Model(spec.load_json(spec.BENCH / "configs"
                                          / f"{name}.json"))


def test_cim_mvm_internlm2_decode_wq():
    """wq of a 32-lane decode step: M = 32, K = N = 2048."""
    m = model("internlm2-1.8b")
    k = spec.kernel_models()["cim_mvm"]
    calls = k.calls(m, 1, np.zeros(32, np.int64), np.ones(32, np.int64))
    assert calls[0] == (2 * 32 * 2048 * 2048,
                        2048 * 2048 // 2 + 32 * 2048 + 2 * 32 * 2048)
    assert calls[0] == (268_435_456, 2_293_760)
    # 7 matmuls in each of 24 layers, and the head on one row per lane
    assert len(calls) == 7 * 24 + 1
    assert calls[-1] == (2 * 32 * 2048 * 92544,
                         2048 * 92544 // 2 + 32 * 2048 + 2 * 32 * 92544)


def test_cim_mvm_granite_prefill_w_up():
    """w_up of a 32-lane, 64-wide prefill step: M = 2048, K = 4096,
    N = 12800."""
    m = model("granite-3-8b-s5")
    k = spec.kernel_models()["cim_mvm"]
    calls = k.calls(m, 64, np.zeros(32, np.int64), np.full(32, 64))
    assert calls[4] == (214_748_364_800, 26_214_400 + 8_388_608 + 52_428_800)
    assert len(calls) == 7 * 5 + 1


def test_paged_attn_internlm2_decode_lane():
    """One decode lane 1023 tokens deep: 1024 live (query, key) pairs."""
    m = model("internlm2-1.8b")
    k = spec.kernel_models()["paged_attn"]
    calls = k.calls(m, 1, np.array([1023, 0]), np.array([1, 0]))
    assert len(calls) == 24
    assert calls[0] == (4 * 16 * 128 * 1024,
                        2 * 2 * 1024 * 8 * 128 + 2 * 2 * 1 * 16 * 128)
    assert calls[0] == (8_388_608, 4_202_496)


def test_paged_attn_granite_prefill_chunk_is_causal():
    """A 64-token chunk on 512 cached: 64·512 + 64·65/2 pairs."""
    m = model("granite-3-8b-s5")
    k = spec.kernel_models()["paged_attn"]
    (flops, nbytes), *_ = k.calls(m, 64, np.array([512]), np.array([64]))
    pairs = 64 * 512 + 64 * 65 // 2
    assert flops == 4 * 32 * 128 * pairs
    assert nbytes == 4 * 576 * 8 * 128 + 4 * 64 * 32 * 128


@pytest.mark.parametrize("name", ["cim_mvm", "paged_attn"])
def test_kernel_model_names_its_peak(name):
    k = spec.kernel_models()[name]
    peaks = spec.peaks("TPU v5 lite")
    assert k.PEAK in peaks and k.PATTERN
