"""BENCHMARK.json and the files the harness finds by name."""
import re
import shutil
import sys
from pathlib import Path

import pytest

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import spec  # noqa: E402

BM = spec.benchmark()
NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}$")
METRICS = BM["end_to_end"] + BM["per_layer"]


def test_names_and_keys():
    assert set(BM) == {"command", "paths", "run_seconds", "configs",
                       "workloads", "end_to_end", "per_layer"}
    for entry in BM["configs"] + BM["workloads"] + METRICS:
        assert NAME.match(entry["name"]), entry["name"]
    e2e = {m["name"] for m in BM["end_to_end"]}
    assert "setup_s" in e2e
    cells = {w["name"] for w in BM["workloads"]}
    for m in METRICS:
        assert m["better"] in ("lower", "higher")
        assert set(m.get("workloads", [])) <= cells
    for m in BM["per_layer"]:
        assert m["moves"] in e2e
    for entry in BM["configs"] + BM["workloads"]:
        assert 0 < len(entry["why"]) <= 200 and "\n" not in entry["why"]
    for m in METRICS:
        assert re.match(r"^[A-Za-z0-9_/%.-]{1,16}$", m["unit"])


@pytest.mark.parametrize("metric", [m["name"] for m in METRICS])
def test_every_metric_has_a_reader_of_its_unit(metric):
    unit = next(m["unit"] for m in METRICS if m["name"] == metric)
    reader = spec.metric_reader(metric)
    assert reader.UNIT == unit and callable(reader.read)


@pytest.mark.parametrize("cell", [w["name"] for w in BM["workloads"]])
def test_every_cell_finds_its_files_and_reports_enough(cell):
    w = spec.workload(BM, cell)
    conf = spec.config(BM, w["config"])
    mix = spec.traffic(w["traffic"])
    assert conf["name"] == w["config"] and mix["loop"] in ("closed",)
    e2e = spec.cell_metrics(BM, cell, trace=False)
    names = {m["name"] for m in e2e}
    assert "setup_s" in names and len(names) >= 2
    per_layer = spec.cell_metrics(BM, cell, trace=True)
    assert per_layer and all(m["moves"] in names for m in per_layer)


def test_config_files_name_their_cuts():
    for c in BM["configs"]:
        conf = spec.load_json(spec.ROOT / c["file"])
        assert conf["reduced"] == c["reduced"]
        for key in c["reduced"]:
            assert conf["source_values"][key] != conf["model"][key]


def test_peaks_refuse_an_unknown_device():
    assert spec.peaks("TPU v5 lite")["int8_ops"] == 393e12
    with pytest.raises(KeyError):
        spec.peaks("TPU v9 imaginary")


@pytest.fixture()
def bench_copy(tmp_path):
    dst = tmp_path / "bench"
    shutil.copytree(spec.BENCH, dst, ignore=shutil.ignore_patterns(
        "__pycache__", "tests", "testdata"))
    return dst


def test_a_metric_added_as_a_file_is_found(bench_copy):
    (bench_copy / "metrics" / "queue_wait_ms.py").write_text(
        'UNIT = "ms"\n\n\ndef read(ctx):\n    return 1e3 * ctx.queue_s\n')
    reader = spec.metric_reader("queue_wait_ms", bench=bench_copy)
    assert reader.UNIT == "ms"
    assert reader.read(type("Ctx", (), {"queue_s": 0.25})()) == 250.0
    with pytest.raises(KeyError):
        spec.metric_reader("no_such_metric", bench=bench_copy)


def test_a_mix_and_a_kernel_added_as_files_are_found(bench_copy):
    (bench_copy / "traffic" / "burst.json").write_text(
        '{"loop": "closed", "concurrency": 8, "pool": 64}')
    assert spec.traffic("burst", bench=bench_copy)["concurrency"] == 8
    (bench_copy / "kernels" / "fused_norm.py").write_text(
        'PATTERN = r"fused_norm"\nPEAK = "bf16_flops"\n\n\n'
        'def calls(m, c, lens, valid):\n    return []\n')
    assert "fused_norm" in spec.kernel_models(bench=bench_copy)


def test_a_cell_added_as_an_entry_is_found():
    bm = {**BM, "workloads": BM["workloads"] + [
        {"name": "internlm2-burst", "config": "internlm2-1.8b",
         "traffic": "burst", "chips": 1, "why": "a test"}],
        "per_layer": BM["per_layer"] + [
            {"name": "queue_wait_ms", "unit": "ms", "better": "lower",
             "source": "host_clock", "layer": "scheduler",
             "moves": "itl_p95_ms", "workloads": ["internlm2-burst"]}]}
    assert spec.workload(bm, "internlm2-burst")["traffic"] == "burst"
    names = {m["name"] for m in spec.cell_metrics(bm, "internlm2-burst",
                                                  trace=True)}
    assert "queue_wait_ms" in names and "host_ms_per_step" in names
    assert "decode_step_ms" not in names
