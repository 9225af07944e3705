"""Golden-value regression: the Fig. 18 PVT sweep and Fig. 21 energy model
pinned against committed CSVs (benchmarks/golden/).

The analog-fidelity core — σ_E across voltage/temperature corners, the ADC
level de-rating, the dual-threshold TD-ADC energy model, the Eq. 4 TOPS/W
curve — was previously pinned only by hand-picked example values; transfer-
curve and PVT-corner behaviour is exactly where CIM reproductions silently
drift (Yin et al. arXiv:2212.04320, Yoshioka et al. arXiv:2411.06079). Any
intentional recalibration must regenerate the CSVs (the generator is the
inline snippet in each CSV's git history / CHANGES.md) and justify the
delta; an unintentional drift fails here loudly.

Tolerances: the macro/energy model is deterministic closed-form python, so
the pins are tight (rtol 1e-6); the paper-anchor checks (40.2 / 18.6
TOPS/W, σ_E = 0.59 LSB) allow the few-percent slack of the fitted model.
"""
import csv
import dataclasses
import os

import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import PROTOTYPE
from repro.core.adc import adc_energy_j, inl_curve
from repro.core.dac import dac_energy_j
from repro.core.energy import macro_throughput_gops, mvm_energy
from repro.core.macro import OperatingPoint

GOLDEN_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "benchmarks", "golden")
RTOL = 1e-6


def _load(name: str) -> dict:
    out = {}
    with open(os.path.join(GOLDEN_DIR, name), newline="") as f:
        for r in csv.DictReader(f):
            out[(r["point"], r["metric"])] = float(r["value"])
    return out


@pytest.fixture(scope="module")
def fig18():
    return _load("fig18_pvt_golden.csv")


@pytest.fixture(scope="module")
def fig21():
    return _load("fig21_energy_golden.csv")


# ---------------------------------------------------------------------------
# Fig. 18: σ_E over PVT corners, gain, and process instances
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vdd", (0.65, 0.8, 0.9, 1.0, 1.2))
def test_fig18_voltage_corners(fig18, vdd):
    m = dataclasses.replace(PROTOTYPE, op=OperatingPoint(vdd=vdd))
    assert m.sigma_e_lsb() == pytest.approx(
        fig18[(f"vdd_{vdd:g}", "sigma_e_lsb")], rel=RTOL)
    assert m.effective_adc_levels() == int(
        fig18[(f"vdd_{vdd:g}", "effective_adc_levels")])


@pytest.mark.parametrize("temp", (-40.0, 25.0, 105.0))
def test_fig18_temperature_corners(fig18, temp):
    m = dataclasses.replace(PROTOTYPE, op=OperatingPoint(temp_c=temp))
    assert m.sigma_e_lsb() == pytest.approx(
        fig18[(f"temp_{temp:g}", "sigma_e_lsb")], rel=RTOL)


@pytest.mark.parametrize("gain", (1.0, 2.0, 3.0, 4.0))
def test_fig18_gain_study(fig18, gain):
    m = dataclasses.replace(PROTOTYPE, gain=gain)
    assert m.sigma_e_lsb() == pytest.approx(
        fig18[(f"gain_{gain:g}", "sigma_e_lsb")], rel=RTOL)
    # σ_E × LSB must SHRINK with gain (the paper's net-win conclusion)
    assert m.sigma_e_lsb() * m.adc_lsb() == pytest.approx(
        fig18[(f"gain_{gain:g}", "sigma_analog")], rel=RTOL)


def test_fig18_gain_sigma_analog_monotone(fig18):
    vals = [fig18[(f"gain_{g:g}", "sigma_analog")] for g in (1, 2, 3, 4)]
    assert vals == sorted(vals, reverse=True)


def test_fig18_process_inl_spread(fig18):
    """8 groups × 5 chips of seeded INL instances (jnp evaluation — runs
    identically in both REPRO_FORCE_JNP legs; the env var only steers
    engine backend selection)."""
    spans = []
    for inst in range(40):
        c = inl_curve(jnp.linspace(0, 1, 256), PROTOTYPE.inl_amp_lsb,
                      seed=inst)
        spans.append(float(jnp.max(jnp.abs(c))))
    assert min(spans) == pytest.approx(
        fig18[("process", "inl_span_best")], rel=1e-5)
    assert max(spans) == pytest.approx(
        fig18[("process", "inl_span_worst")], rel=1e-5)
    # every instance stays within the measured ±1.10 LSB bound
    assert max(spans) <= PROTOTYPE.inl_amp_lsb + 1e-6


def test_fig18_paper_anchor():
    """The calibration anchor itself: σ_E = 0.59 LSB at (0.9 V, 25 °C)."""
    assert PROTOTYPE.sigma_e_lsb() == pytest.approx(0.59, rel=1e-3)


# ---------------------------------------------------------------------------
# Fig. 21: energy efficiency / clock / throughput over voltage
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("vdd", (0.65, 0.75, 0.9, 1.05, 1.2))
def test_fig21_voltage_sweep(fig21, vdd):
    m = dataclasses.replace(PROTOTYPE, op=OperatingPoint(vdd=vdd))
    rep = mvm_energy(m, 144)
    key = f"vdd_{vdd:g}"
    assert rep.tops_per_w == pytest.approx(fig21[(key, "tops_per_w")],
                                           rel=RTOL)
    assert m.clock_hz() / 1e6 == pytest.approx(fig21[(key, "fclk_mhz")],
                                               rel=RTOL)
    assert macro_throughput_gops(m) == pytest.approx(fig21[(key, "gops")],
                                                     rel=RTOL)
    assert rep.e_mvm_j == pytest.approx(fig21[(key, "e_mvm_j")], rel=RTOL)
    assert rep.e_adc_j == pytest.approx(fig21[(key, "e_adc_j")], rel=RTOL)


def test_fig21_adc_dual_threshold_gating(fig21):
    gated = adc_energy_j(PROTOTYPE, dual_threshold=True)
    ungated = adc_energy_j(PROTOTYPE, dual_threshold=False)
    assert gated == pytest.approx(
        fig21[("nominal", "adc_energy_gated_j")], rel=RTOL)
    assert ungated == pytest.approx(
        fig21[("nominal", "adc_energy_ungated_j")], rel=RTOL)
    # the measured 55.8 % main-path power gating (§IV)
    assert gated / ungated == pytest.approx(1.0 - 0.558, rel=1e-6)


@pytest.mark.parametrize("sparsity", (0.0, 0.5, 0.9))
def test_fig21_dac_sparsity_share(fig21, sparsity):
    """Sparsity-dependent DAC energy share (paper: 2.4–14.6 %). The codes
    and mask come from a seeded numpy Generator, whose stream no jax
    release can move."""
    rng = np.random.default_rng(0)
    codes = rng.integers(0, 16, 4096).astype(np.float32)
    mask = rng.random(4096) >= sparsity
    e_dac = float(dac_energy_j(jnp.asarray(codes * mask), PROTOTYPE))
    e_tot = mvm_energy(PROTOTYPE, 144).e_mvm_j
    share = e_dac / (e_tot + e_dac)
    assert share == pytest.approx(
        fig21[(f"dac_sparsity_{sparsity:g}", "dac_share")], rel=1e-5)


def test_fig21_paper_anchors(fig21):
    """Both measured Fig. 21 endpoints: 40.2 TOPS/W @ 0.65 V and
    18.6 TOPS/W @ 1.2 V (the two-point calibration of the V^α fit)."""
    assert fig21[("vdd_0.65", "tops_per_w")] == pytest.approx(40.2, rel=0.01)
    assert fig21[("vdd_1.2", "tops_per_w")] == pytest.approx(18.6, rel=0.01)
