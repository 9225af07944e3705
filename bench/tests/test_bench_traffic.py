"""Traffic generation: one general generator reads each mix's data file."""
import statistics
import sys
from pathlib import Path

import numpy as np
import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import spec, traffic  # noqa: E402

MIXES = sorted(p.stem for p in (spec.BENCH / "traffic").glob("*.json"))
BIG_SEED = 2**40 + 12345


@pytest.mark.parametrize("mix_name", MIXES)
def test_same_seed_same_requests(mix_name):
    mix = spec.traffic(mix_name)
    a = traffic.Plan(mix, BIG_SEED, 92544, 16)
    b = traffic.Plan(mix, BIG_SEED, 92544, 16)
    assert [r.prompt for r in (a.next_request() for _ in range(20))] == \
        [r.prompt for r in (b.next_request() for _ in range(20))]
    wa, wb = a.first_wave(mix["concurrency"]), b.first_wave(mix["concurrency"])
    assert [(w.prompt, w.max_new, w.in_setup) for w in wa] == \
        [(w.prompt, w.max_new, w.in_setup) for w in wb]


@pytest.mark.parametrize("mix_name", MIXES)
def test_seeds_share_the_sizes_not_the_order(mix_name):
    """Every seed serves the same sizes: the stream's and the first wave's.
    The seed changes the tokens and the order of the first wave."""
    mix = spec.traffic(mix_name)
    a = traffic.Plan(mix, 7, 92544, 16)
    b = traffic.Plan(mix, BIG_SEED, 92544, 16)
    ra = [a.next_request() for _ in range(50)]
    rb = [b.next_request() for _ in range(50)]
    assert [(len(x.prompt), x.max_new) for x in ra] == \
        [(len(x.prompt), x.max_new) for x in rb]
    assert ra[0].prompt != rb[0].prompt
    n = mix["concurrency"]
    wa, wb = a.first_wave(n), b.first_wave(n)
    size = lambda w: (w.key, len(w.prompt), w.max_new,  # noqa: E731
                      w.in_setup)
    assert sorted(map(size, wa)) == sorted(map(size, wb))
    assert [w.key for w in wa] != [w.key for w in wb]


@pytest.mark.parametrize("mix_name", MIXES)
def test_any_run_of_the_stream_spreads_over_the_strata(mix_name):
    """32 consecutive requests, from any start, have a median prompt and
    output length within a few strata of the mix's median."""
    mix = spec.traffic(mix_name)
    plan = traffic.Plan(mix, 3, 92544, 16)
    sizes = np.array([plan.sizes(j) for j in range(400)])
    for start in (0, 37, 200, 368):
        run = sizes[start:start + 32]
        for col, part in enumerate(("prompt", "output")):
            med = np.median(run[:, col])
            assert abs(np.log(med / mix[part]["median"])) \
                < 0.3 * mix[part]["sigma"]


@pytest.mark.parametrize("mix_name", MIXES)
@pytest.mark.parametrize("part", ["prompt", "output"])
def test_lengths_follow_the_stated_distribution(mix_name, part):
    mix = spec.traffic(mix_name)
    dist = mix[part]
    n = mix["pool"]
    got = traffic.lengths(dist, n)
    stream = traffic.Plan(mix, 5, 92544, 16)
    col = ("prompt", "output").index(part)
    drawn = [stream.sizes(j)[col] for j in range(n)]
    assert abs(statistics.median(drawn) - dist["median"]) \
        <= 0.02 * dist["median"]
    assert len(got) == n
    assert got.min() >= dist["min"] and got.max() <= dist["max"]
    assert abs(statistics.median(got) - dist["median"]) \
        <= 0.02 * dist["median"]
    # the spread of a lognormal: log-quartiles at ±0.674 sigma
    q1, q3 = np.percentile(np.log(got), [25, 75])
    assert abs((q3 - q1) - 2 * 0.6745 * dist["sigma"]) < 0.05


@pytest.mark.parametrize("mix_name", MIXES)
def test_first_wave_starts_near_steady_state(mix_name):
    mix = spec.traffic(mix_name)
    plan = traffic.Plan(mix, 11, 92544, 16)
    wave = plan.first_wave(mix["concurrency"])
    assert len(wave) == mix["concurrency"]
    for item in wave:
        assert 1 <= len(item.prompt) <= mix["prompt"]["max"]
        assert 1 <= item.max_new <= mix["output"]["max"]
        assert all(0 <= t < 92544 for t in item.prompt)
    # some workers are mid-output (prefilled in set-up), and on a
    # decode-heavy mix most of them are
    in_setup = sum(w.in_setup for w in wave)
    assert 0 < in_setup <= len(wave)
