"""Per-PR perf trajectory from BENCH_ci.json artifacts → markdown report.

The CI bench-smoke job has been uploading one BENCH_ci.json per run since
PR 2 (schema pico-ram/kernel_bench/v1); this module turns those point
measurements into the trajectory the ROADMAP asks for. Two modes:

  one-shot over explicit files (oldest → newest):
    PYTHONPATH=src python -m repro.analysis.bench_trend \
        run1/BENCH_ci.json run2/BENCH_ci.json --out TREND.md

  accumulating history (what CI runs — the previous run's history artifact
  is downloaded when present, the current bench is appended, and both the
  updated history and the rendered report are re-uploaded):
    PYTHONPATH=src python -m repro.analysis.bench_trend \
        --history bench_history.jsonl --append BENCH_ci.json \
        --label "$GITHUB_SHA" --out TREND.md

Tracked columns (parsed from the bench rows; missing rows render as "—"):
  * decode tokens/s — the --small packed decode sweep's wall time converted
    to tokens/second (interpret-mode on CPU CI: a structural trend, not TPU
    absolute perf — a 10× regression still shows as a 10× regression);
  * weight-HBM bytes of the packed decode shape and its ×-less-HBM factor
    vs int8 (the nibble-packing win — exact byte counts, platform-free);
  * fused-vs-einsum σ ratio of the stochastic kernel's ADC-chain error (the
    in-kernel PRNG distributional-agreement number the engine tests pin —
    drift here means a PRNG/transfer regression);
  * fused stochastic kernel wall µs;
  * (schema v2) the serving sweep: paged-engine decode tok/s from the
    end-to-end runtime.server drain, and the resident KV-cache bytes at
    25 % slot occupancy — paged pool vs the monolithic slot cache, with the
    ×-less-HBM factor (exact byte counts, platform-free);
  * (schema v3) the paged-attention sweep: the paged engine drained on the
    Pallas flash attention backend (kernel decode tok/s next to the exact
    backend's), and the peak score-tensor bytes of the LARGEST swept
    window — exact materializes [B, C, KH, G, W] (O(W)), the kernel keeps
    one [C·G, block] tile (O(block)); the ×-less factor is the memory
    probe the acceptance criteria pin;
  * (schema v4) the autotune sweep: tuned-vs-default speedup of the
    W=4096 decode paged-attention family (`paged_attn_decode_w4096_tuned`
    vs its `_default` twin, from `kernel_bench --autotune`) — the number
    the bench-smoke job gates at ≥ 1.25×;
  * (schema v5) the shared-prefix serving row: peak decode lanes of the
    prefix-sharing paged pool vs the same pool with sharing disabled (the
    ×-concurrency factor the bench-smoke job gates at > 5×), plus the
    prefill tokens the trie absorbed — deterministic lane/token counts,
    platform-free;
  * (schema v6) the spec-decode serving row: speculative-vs-plain greedy
    decode tok/s on the paged engine with the ngram drafter (warm-timed
    legs, bit-identical outputs asserted in the bench) — the speedup the
    bench-smoke job gates at ≥ 1.5×, plus the mean accepted length per
    verify step (1 + accepted drafts, the number the speedup is made of);
  * (schema v7) the energy-pareto row: serving energy/token (Eq. 4 over
    the calibration traffic profile) of uniform 4b×4b BP at native ADC
    resolution vs the searched per-site mixed-precision manifest, the
    ×-energy win the bench-smoke job gates at ≥ 1.3×, and the
    accuracy-proxy delta (held-out logit KL vs float: mixed − uniform,
    bounded by the search's kl_budget) — deterministic model numbers,
    platform-free.
"""
from __future__ import annotations

import argparse
import json
import os
import re
import sys


def load_bench(path: str) -> dict:
    with open(path) as f:
        doc = json.load(f)
    schema = str(doc.get("schema", ""))
    if not schema.startswith("pico-ram/kernel_bench/"):
        raise ValueError(f"{path}: unexpected schema {schema!r}")
    if not doc.get("rows"):
        raise ValueError(f"{path}: no bench rows")
    return doc


def extract_metrics(doc: dict) -> dict:
    """One BENCH_ci.json document → the tracked scalar metrics."""
    out: dict = {}
    for r in doc["rows"]:
        name, us, derived = r["name"], float(r["us"]), str(r.get("derived", ""))
        m = re.match(r"decode_packed_m(\d+)_k(\d+)_n(\d+)", name)
        if m and "decode_tok_s" not in out:
            toks = int(m.group(1))
            out["decode_shape"] = f"m{m.group(1)}_k{m.group(2)}_n{m.group(3)}"
            out["decode_tok_s"] = toks / us * 1e6
            wb = re.search(r"w_bytes\s+(\d+)->(\d+)\s+\(([\d.]+)x", derived)
            if wb:
                out["w_bytes_packed"] = int(wb.group(2))
                out["w_bytes_int8"] = int(wb.group(1))
                out["hbm_win"] = float(wb.group(3))
        if name.startswith("kernel_pallas_noisy"):
            out["noisy_us"] = us
            sr = re.search(r"ratio=([\d.]+)", derived)
            if sr:
                out["sigma_ratio"] = float(sr.group(1))
        if name.startswith("kernel_ref_jnp"):
            out["ref_us"] = us
        if name.startswith("serve_decode_paged_attnkernel"):
            sd = re.search(r"decode_tok_s=([\d.]+)", derived)
            if sd:
                out["attn_kernel_tok_s"] = float(sd.group(1))
        elif name.startswith("serve_decode_paged"):
            sd = re.search(r"decode_tok_s=([\d.]+)", derived)
            if sd:
                out["serve_decode_tok_s"] = float(sd.group(1))
        m4 = re.match(r"paged_attn_decode_w(\d+)_tuned", name)
        if m4:
            sp = re.search(r"speedup=([\d.]+)x", derived)
            if sp and int(m4.group(1)) >= out.get("tune_window", 0):
                out["tune_window"] = int(m4.group(1))
                out["tune_speedup"] = float(sp.group(1))
            continue  # the tuned/default pair carries no score-bytes probe
        if name.endswith("_default"):
            continue
        m3 = re.match(r"paged_attn_decode_w(\d+)", name)
        if m3:
            w = int(m3.group(1))
            sb = re.search(
                r"score_bytes\s+exact=(\d+)\s+kernel=(\d+)\s+\((\d+)x",
                derived)
            if sb and w >= out.get("score_window", 0):
                out["score_window"] = w
                out["score_bytes_exact"] = int(sb.group(1))
                out["score_bytes_kernel"] = int(sb.group(2))
                out["score_win"] = float(sb.group(3))
        if name.startswith("serve_shared_prefix"):
            pl = re.search(r"shared=(\d+) nosharing=(\d+) \(([\d.]+)x",
                           derived)
            if pl:
                out["prefix_lanes"] = int(pl.group(1))
                out["prefix_lanes_base"] = int(pl.group(2))
                out["prefix_win"] = float(pl.group(3))
            ts = re.search(r"prefill_tok_saved=(\d+)", derived)
            if ts:
                out["prefix_tok_saved"] = int(ts.group(1))
        if name.startswith("serve_spec_decode"):
            sp = re.search(r"speedup=([\d.]+)x", derived)
            if sp:
                out["spec_speedup"] = float(sp.group(1))
            ml = re.search(r"mean_accept_len=([\d.]+)", derived)
            if ml:
                out["spec_accept_len"] = float(ml.group(1))
        if name.startswith("energy_pareto"):
            ep = re.search(
                r"uniform_pj_tok=([\d.]+)\|mixed_pj_tok=([\d.]+)\|"
                r"energy_win=([\d.]+)x", derived)
            if ep:
                out["uniform_pj_tok"] = float(ep.group(1))
                out["mixed_pj_tok"] = float(ep.group(2))
                out["energy_win"] = float(ep.group(3))
            kd = re.search(r"kl_uniform=([\d.]+)\|kl_mixed=([\d.]+)",
                           derived)
            if kd:
                out["energy_kl_delta"] = float(kd.group(2)) \
                    - float(kd.group(1))
        if name.startswith("serve_kv_bytes_occ25"):
            kb = re.search(
                r"kv_bytes\s+slot=(\d+)\s+paged=(\d+)\s+\(([\d.]+)x", derived)
            if kb:
                out["kv_bytes_slot"] = int(kb.group(1))
                out["kv_bytes_paged"] = int(kb.group(2))
                out["kv_win"] = float(kb.group(3))
    return out


def entry_from_bench(path: str, label: str | None = None) -> dict:
    doc = load_bench(path)
    return {
        "label": label or os.path.basename(os.path.dirname(path) or path),
        "jax": doc.get("jax"),
        "backend": doc.get("backend"),
        "metrics": extract_metrics(doc),
    }


def load_history(path: str) -> list[dict]:
    if not os.path.exists(path):
        return []
    entries = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                entries.append(json.loads(line))
    return entries


def save_history(path: str, entries: list[dict]) -> None:
    with open(path, "w") as f:
        for e in entries:
            f.write(json.dumps(e) + "\n")


def _fmt(v, spec: str = "{:.3f}") -> str:
    return "—" if v is None else spec.format(v)


def render_markdown(entries: list[dict]) -> str:
    lines = [
        "# kernel_bench perf trajectory",
        "",
        "Interpret-mode CPU CI numbers — structural trend, not TPU absolute "
        "perf. Byte counts and the σ ratio are platform-free.",
        "",
        "| run | decode tok/s | packed weight HBM B | vs int8 | "
        "fused σ ratio | fused noisy µs | serve tok/s | attn-kernel tok/s | "
        "paged KV B @25% | vs slot | score B (kernel) | vs exact | "
        "tuned speedup | prefix lanes | prefill tok saved | spec speedup | "
        "accept len | mixed pJ/tok | energy win | ΔKL proxy |",
        "|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---|---"
        "|---|---|---|---|",
    ]
    for e in entries:
        m = e.get("metrics", {})
        prefix_lanes = None
        if m.get("prefix_lanes") is not None:
            prefix_lanes = (f"{m['prefix_lanes']} vs "
                            f"{m.get('prefix_lanes_base', '?')} "
                            f"({m.get('prefix_win', 0):.1f}×)")
        lines.append(
            "| {} | {} | {} | {} | {} | {} | {} | {} | {} | {} | {} | {} "
            "| {} | {} | {} | {} | {} | {} | {} | {} |"
            .format(
                str(e.get("label", "?"))[:24],
                _fmt(m.get("decode_tok_s"), "{:.0f}"),
                _fmt(m.get("w_bytes_packed"), "{:d}"),
                _fmt(m.get("hbm_win"), "{:.2f}×"),
                _fmt(m.get("sigma_ratio")),
                _fmt(m.get("noisy_us"), "{:.1f}"),
                _fmt(m.get("serve_decode_tok_s"), "{:.1f}"),
                _fmt(m.get("attn_kernel_tok_s"), "{:.1f}"),
                _fmt(m.get("kv_bytes_paged"), "{:d}"),
                _fmt(m.get("kv_win"), "{:.2f}×"),
                _fmt(m.get("score_bytes_kernel"), "{:d}"),
                _fmt(m.get("score_win"), "{:.0f}×"),
                _fmt(m.get("tune_speedup"), "{:.2f}×"),
                prefix_lanes or "—",
                _fmt(m.get("prefix_tok_saved"), "{:d}"),
                _fmt(m.get("spec_speedup"), "{:.2f}×"),
                _fmt(m.get("spec_accept_len"), "{:.2f}"),
                _fmt(m.get("mixed_pj_tok"), "{:.0f}"),
                _fmt(m.get("energy_win"), "{:.2f}×"),
                _fmt(m.get("energy_kl_delta"), "{:+.4f}"),
            ))
    shapes = {e.get("metrics", {}).get("decode_shape") for e in entries}
    shapes.discard(None)
    if shapes:
        lines += ["", f"decode shape(s): {', '.join(sorted(shapes))}"]
    windows = {e.get("metrics", {}).get("score_window") for e in entries}
    windows.discard(None)
    if windows:
        lines += ["", "score-tensor probe window(s): "
                  + ", ".join(str(w) for w in sorted(windows))]
    lines.append("")
    return "\n".join(lines)


def render_pareto_markdown(manifest: dict) -> str:
    """Energy/accuracy Pareto section from a precision-search manifest —
    the deployment artifact `serve.py --precision-manifest` consumes, so
    the table describes exactly what `ServingConfig` dispatches."""
    from repro.analysis.precision_search import pareto_points
    m = manifest["metrics"]
    pts = pareto_points(manifest)
    win = m["energy_win"]
    lines = [
        "## Energy/accuracy Pareto (mixed analog precision)",
        "",
        f"Per-site precision manifest (schema `{manifest['schema']}`, "
        f"arch `{manifest['arch']}`, seed {manifest['seed']}) served "
        "through `ServingConfig(precision_manifest=…)` → "
        "`CIMConfig.site_overrides`. Energy is Eq. 4 over the calibration "
        "traffic profile; the accuracy proxy is held-out logit KL to the "
        f"float model (budget {m['kl_budget']} over the uniform config).",
        "",
        "| config | pJ/token | vs uniform | KL vs float |",
        "|---|---|---|---|",
    ]
    base = pts[0]["pj_per_token"]
    for p in pts:
        lines.append("| {} | {:.1f} | {:.3f}× | {:.4f} |".format(
            p["config"], p["pj_per_token"],
            base / max(p["pj_per_token"], 1e-30), p["kl"]))
    sites = ", ".join(
        f"{name}={e['adc_levels']}" for name, e in
        sorted(manifest["sites"].items()))
    lines += [
        "",
        f"mixed config: {win:.3f}× lower energy/token "
        f"({(1 - 1 / win) * 100:.1f} % saved) at iso-accuracy-proxy.",
        "",
        f"per-site ADC levels: {sites}",
        "",
    ]
    return "\n".join(lines)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("bench", nargs="*",
                    help="BENCH_ci.json files, oldest first (one-shot mode)")
    ap.add_argument("--history", default=None, metavar="JSONL",
                    help="accumulating history file (read if present, "
                         "re-written with --append applied)")
    ap.add_argument("--append", default=None, metavar="BENCH_JSON",
                    help="append this bench document to --history")
    ap.add_argument("--label", default=None,
                    help="label for the appended entry (e.g. the git sha)")
    ap.add_argument("--out", default="TREND.md",
                    help="markdown report path")
    ap.add_argument("--max-entries", type=int, default=200,
                    help="keep only the newest N history entries")
    ap.add_argument("--precision-manifest", default=None, metavar="JSON",
                    dest="precision_manifest",
                    help="append the energy/accuracy Pareto section "
                         "rendered from this precision-search manifest")
    args = ap.parse_args(argv)

    if bool(args.history) != bool(args.append) and not args.bench:
        ap.error("--history and --append go together")
    entries: list[dict] = []
    if args.history:
        entries = load_history(args.history)
        if args.append:
            entries.append(entry_from_bench(args.append, args.label))
            entries = entries[-args.max_entries:]
            save_history(args.history, entries)
    for path in args.bench:
        entries.append(entry_from_bench(path))
    if not entries:
        ap.error("nothing to render: pass bench files or --history/--append")
    md = render_markdown(entries)
    if args.precision_manifest:
        from repro.analysis.precision_search import load_manifest
        manifest = load_manifest(args.precision_manifest)
        if manifest is not None:
            md += "\n" + render_pareto_markdown(manifest)
    with open(args.out, "w") as f:
        f.write(md)
    print(f"wrote {args.out} ({len(entries)} run(s))")
    return 0


if __name__ == "__main__":
    sys.exit(main())
