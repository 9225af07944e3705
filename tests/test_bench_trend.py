"""analysis.bench_trend: BENCH_ci.json ingestion, history accumulation and
the rendered markdown perf-trajectory table (the bench-smoke CI artifact)."""
import json

import pytest

from repro.analysis import bench_trend


def _doc(us_decode=400.0, ratio=1.02):
    return {
        "schema": "pico-ram/kernel_bench/v1",
        "jax": "0.4.37",
        "backend": "cpu",
        "rows": [
            {"name": "kernel_ref_jnp_576x64", "us": 120.0, "derived": "oracle"},
            {"name": "kernel_pallas_noisy_m32_k288_n64", "us": 700.0,
             "derived": f"einsum_noisy_us=900.0|err_sigma fused=0.100 "
                        f"einsum=0.098 ratio={ratio:.3f}"},
            {"name": "decode_packed_m8_k576_n128", "us": us_decode,
             "derived": "unpacked_us=500.0|w_bytes 73728->36864 "
                        "(2.00x less HBM)"},
            {"name": "serve_decode_paged_s4_r4", "us": 90000.0,
             "derived": "decode_tok_s=11.0|prefill_tok_s=30.4|steps=6"},
            {"name": "serve_kv_bytes_occ25_s4", "us": 1000.0,
             "derived": "kv_bytes slot=262144 paged=16384 "
                        "(16.00x less HBM)"},
            # schema-v3 paged-attention sweep rows: the extractor must keep
            # the LARGEST window's score-byte probe and must not let the
            # attnkernel serving row clobber the exact-path serve tok/s
            {"name": "paged_attn_decode_w64", "us": 800.0,
             "derived": "exact_us=300.0|score_bytes exact=2048 kernel=64 "
                        "(32x less)"},
            {"name": "paged_attn_decode_w256", "us": 900.0,
             "derived": "exact_us=600.0|score_bytes exact=8192 kernel=64 "
                        "(128x less)"},
            {"name": "serve_decode_paged_attnkernel_s4_r4", "us": 95000.0,
             "derived": "decode_tok_s=9.5|exact_tok_s=11.0|ratio=0.864"},
            # schema-v4 autotune rows: the tuned/default pair must feed the
            # speedup column only — neither row carries a score-byte probe,
            # and the w4096 name must NOT clobber the score-window metric
            {"name": "paged_attn_decode_w4096_default", "us": 34000.0,
             "derived": "block_size=16|kblocks=1|row_tile=None"},
            {"name": "paged_attn_decode_w4096_tuned", "us": 5600.0,
             "derived": "default_us=34000.0|speedup=6.07x|block_size=128|"
                        "kblocks=1|row_tile=None"},
            {"name": "cim_mvm_m64_g2_n64_tuned", "us": 206.0,
             "derived": "default_us=285.0|speedup=1.38x|bm=128|bn=64"},
            # schema-v5 shared-prefix serving row: decode-lane concurrency
            # of the prefix-sharing pool vs the sharing-disabled pool
            {"name": "serve_shared_prefix_s8_r7", "us": 6000.0,
             "derived": "peak_lanes shared=7 nosharing=1 (7.0x)|"
                        "prefill_tok_saved=336|"
                        "preempt shared=0 nosharing=21"},
            # schema-v6 spec-decode serving row: speculative-vs-plain
            # greedy tok/s plus the accept-length statistics
            {"name": "serve_spec_decode_k4_s2", "us": 400.0,
             "derived": "spec_tok_s=2511.6|plain_tok_s=1128.8|"
                        "speedup=2.23x|accept_rate=0.47|"
                        "mean_accept_len=2.87|hist=0:50;1:6;2:7;3:2;4:45"},
            # schema-v7 energy-pareto row: uniform-vs-mixed serving
            # energy/token with the precision search's KL-proxy numbers
            {"name": "energy_pareto_mixed_precision", "us": 2.2e7,
             "derived": "uniform_pj_tok=26692.7|mixed_pj_tok=18448.8|"
                        "energy_win=1.447x|kl_uniform=2.2014|"
                        "kl_mixed=2.2163|kl_budget=0.080|levels=wq:128"},
        ],
    }


def test_extract_metrics():
    m = bench_trend.extract_metrics(_doc())
    assert m["decode_tok_s"] == pytest.approx(8 / 400.0 * 1e6)
    assert m["w_bytes_packed"] == 36864
    assert m["w_bytes_int8"] == 73728
    assert m["hbm_win"] == pytest.approx(2.0)
    assert m["sigma_ratio"] == pytest.approx(1.02)
    assert m["noisy_us"] == 700.0
    assert m["ref_us"] == 120.0
    # schema-v2 serving sweep rows
    assert m["serve_decode_tok_s"] == pytest.approx(11.0)
    assert m["kv_bytes_slot"] == 262144
    assert m["kv_bytes_paged"] == 16384
    assert m["kv_win"] == pytest.approx(16.0)
    # schema-v3 paged-attention sweep: largest window wins; the attnkernel
    # serving row fills its own metric without clobbering serve_decode_tok_s
    assert m["attn_kernel_tok_s"] == pytest.approx(9.5)
    assert m["score_window"] == 256
    assert m["score_bytes_exact"] == 8192
    assert m["score_bytes_kernel"] == 64
    assert m["score_win"] == pytest.approx(128.0)
    # schema-v4 autotune pair: speedup extracted from the tuned row; the
    # w4096 tuned/default names don't disturb the score-window probe above
    assert m["tune_window"] == 4096
    assert m["tune_speedup"] == pytest.approx(6.07)
    # schema-v5 shared-prefix serving row
    assert m["prefix_lanes"] == 7
    assert m["prefix_lanes_base"] == 1
    assert m["prefix_win"] == pytest.approx(7.0)
    assert m["prefix_tok_saved"] == 336
    # schema-v6 spec-decode serving row
    assert m["spec_speedup"] == pytest.approx(2.23)
    assert m["spec_accept_len"] == pytest.approx(2.87)
    # schema-v7 energy-pareto row
    assert m["uniform_pj_tok"] == pytest.approx(26692.7)
    assert m["mixed_pj_tok"] == pytest.approx(18448.8)
    assert m["energy_win"] == pytest.approx(1.447)
    assert m["energy_kl_delta"] == pytest.approx(2.2163 - 2.2014)


def test_extract_metrics_tolerates_missing_rows():
    doc = _doc()
    doc["rows"] = doc["rows"][:1]
    m = bench_trend.extract_metrics(doc)
    assert "decode_tok_s" not in m and "sigma_ratio" not in m
    md = bench_trend.render_markdown([{"label": "x", "metrics": m}])
    assert "—" in md


def test_load_bench_rejects_bad_schema(tmp_path):
    p = tmp_path / "bad.json"
    p.write_text(json.dumps({"schema": "other/v1", "rows": [{}]}))
    with pytest.raises(ValueError):
        bench_trend.load_bench(str(p))


def test_history_append_and_render(tmp_path):
    hist = tmp_path / "hist.jsonl"
    for i, label in enumerate(("run-a", "run-b")):
        b = tmp_path / f"b{i}.json"
        b.write_text(json.dumps(_doc(us_decode=400.0 + 100 * i)))
        rc = bench_trend.main(["--history", str(hist), "--append", str(b),
                               "--label", label,
                               "--out", str(tmp_path / "TREND.md")])
        assert rc == 0
    entries = bench_trend.load_history(str(hist))
    assert [e["label"] for e in entries] == ["run-a", "run-b"]
    md = (tmp_path / "TREND.md").read_text()
    assert "run-a" in md and "run-b" in md
    assert "20000" in md    # 8 tok / 400 µs
    assert "2.00×" in md and "36864" in md
    assert "9.5" in md and "128×" in md    # v3 attn-kernel + score probe
    assert "6.07×" in md                   # v4 tuned-vs-default speedup
    assert "7 vs 1 (7.0×)" in md and "336" in md  # v5 shared-prefix row
    assert "2.23×" in md and "2.87" in md         # v6 spec-decode row
    assert "1.45×" in md and "+0.0149" in md      # v7 energy-pareto row
    # table stays well-formed: every data row has the 20 columns
    rows = [ln for ln in md.splitlines() if ln.startswith("| run-")]
    assert all(ln.count("|") == 21 for ln in rows)


def test_one_shot_mode(tmp_path):
    b1 = tmp_path / "one" / "BENCH_ci.json"
    b1.parent.mkdir()
    b1.write_text(json.dumps(_doc()))
    out = tmp_path / "T.md"
    assert bench_trend.main([str(b1), "--out", str(out)]) == 0
    assert "kernel_bench perf trajectory" in out.read_text()


def test_pareto_section_from_manifest(tmp_path):
    """--precision-manifest appends the Pareto section rendered from the
    deployment manifest; a malformed manifest degrades to no section (the
    same warn-and-serve-defaults contract as the Server)."""
    from repro.analysis import precision_search as ps
    manifest = {
        "schema": ps.MANIFEST_SCHEMA, "arch": "internlm2-1.8b", "seed": 0,
        "act_qmax": 15, "base_adc_levels": 362,
        "default": {"scale": 1.0, "zero_point": 0.0},
        "sites": {"wq": {"act_scale": 0.5, "act_zero_point": 0.0,
                         "adc_levels": 128, "scheme": "bp",
                         "per_channel": None, "k": 64, "m": 8, "calls": 2}},
        "metrics": {"uniform_pj_per_token": 100.0,
                    "mixed_pj_per_token": 69.0, "energy_win": 100.0 / 69.0,
                    "kl_uniform": 1.0, "kl_proxy": 1.05, "kl_budget": 0.08,
                    "trace": []},
    }
    mp = tmp_path / "manifest.json"
    ps.save_manifest(str(mp), manifest)
    b1 = tmp_path / "BENCH_ci.json"
    b1.write_text(json.dumps(_doc()))
    out = tmp_path / "T.md"
    assert bench_trend.main([str(b1), "--out", str(out),
                             "--precision-manifest", str(mp)]) == 0
    md = out.read_text()
    assert "Energy/accuracy Pareto" in md
    assert "uniform 4b×4b BP (362-level ADC)" in md
    assert "1.449×" in md and "wq=128" in md
    # malformed manifest: section silently absent, render still succeeds
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    with pytest.warns(UserWarning, match="precision manifest"):
        assert bench_trend.main([str(b1), "--out", str(out),
                                 "--precision-manifest", str(bad)]) == 0
    assert "Energy/accuracy Pareto" not in out.read_text()
