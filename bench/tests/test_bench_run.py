"""Whole runs on the CPU at a test size: the comparison that decides
`correct` passes the sound program and fails the control and each fault
of the timed path; without a TPU the benchmark prints no result."""
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
ROOT = BENCH.parent
FAULTS = ["stale", "half_batch", "token"]


def _env():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env["XLA_FLAGS"] = " ".join(filter(None, (
        env.get("XLA_FLAGS"), "--xla_allow_excess_precision=false")))
    env["PYTHONPATH"] = os.pathsep.join(
        filter(None, (str(ROOT / "src"), env.get("PYTHONPATH"))))
    return env


@pytest.fixture(scope="module")
def runs():
    out = subprocess.run(
        [sys.executable, str(BENCH / "tests" / "cpu_cell.py"),
         "internlm2-1.8b", "longgen", "sound", "control", *FAULTS],
        env=_env(), capture_output=True, text=True, timeout=900)
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [json.loads(x) for x in out.stdout.splitlines()
             if x.startswith("{")]
    return {r["fault"]: r for r in lines}


def test_sound_program_matches_the_reference(runs):
    r = runs["sound"]
    assert r["correct"], r
    assert r["compared"]["logit_gap"]["value"] == 0.0
    assert r["compared"]["tokens_compared"]["value"] >= 20


def test_control_fails(runs):
    """The reference with float8 keys and values in the program's place,
    through the same comparison that decides `correct`."""
    r = runs["control"]
    assert not r["correct"], r
    assert r["compared"]["logit_gap"]["value"] > r["limit"]
    assert r["compared"]["tokens_compared"]["value"] >= 20


@pytest.mark.parametrize("fault", FAULTS)
def test_fault_of_the_timed_path_fails(runs, fault):
    r = runs[fault]
    assert not r["correct"], r
    assert r["compared"]["logit_gap"]["value"] > r["limit"]


def _run_py(cwd):
    return subprocess.run(
        [sys.executable, "bench/run.py", "--workload", "internlm2-longgen",
         "--seed", str(2**35 + 1), "--seconds", "1", "--trace", "0"],
        cwd=cwd, env=_env(), capture_output=True, text=True, timeout=300)


def test_no_tpu_no_result():
    out = _run_py(ROOT)
    assert out.returncode != 0
    assert not [x for x in out.stdout.splitlines() if x.startswith("{")]


def test_benchmark_files_alone_give_no_result(tmp_path):
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(BENCH, tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run_py(tmp_path)
    assert out.returncode != 0
    assert not [x for x in out.stdout.splitlines() if x.startswith("{")]
