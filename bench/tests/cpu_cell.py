"""Run a cell's whole harness at a test size on the CPU, optionally with
the timed path broken underneath, and print one JSON line per run.

    JAX_PLATFORMS=cpu XLA_FLAGS=--xla_allow_excess_precision=false \\
        python bench/tests/cpu_cell.py internlm2-1.8b longgen sound stale

The configuration keeps its arithmetic (the CIM macro, the static grid,
the attention order) and shrinks to the program's smoke widths: 2 layers,
d 128, 4/2 heads, d_ff 256, vocab 512, 4 slots of 256 tokens. Faults:

  sound       nothing broken
  control     nothing broken, and the control in the program's place: the
              tokens compared are the float8-KV reference's first choices
  stale       the step returns the KV pool it was given (state unchanged)
  half_batch  the step computes the first half of the lanes only; the other
              half get the first half's logits
  token       every fifth step, each lane's top logit moves to the next
              token id (a token altered where it is produced)
"""
from __future__ import annotations

import copy
import json
import sys
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))

from benchlib import harness, spec  # noqa: E402

SMOKE = {"hidden_size": 128, "num_attention_heads": 4,
         "num_key_value_heads": 2, "intermediate_size": 256,
         "vocab_size": 512, "num_hidden_layers": 2}


def small(config: str, mix_name: str):
    conf = copy.deepcopy(spec.load_json(spec.BENCH / "configs"
                                        / f"{config}.json"))
    conf["model"].update(SMOKE)
    conf["program"]["overrides"] = {"n_layers": SMOKE["num_hidden_layers"]}
    conf["correct"]["min_tokens"] = 20
    flags = conf["serving"]["flags"]
    flags[flags.index("--full")] = "--smoke"
    for flag, value in (("--slots", "4"), ("--max-len", "256"),
                        ("--token-budget", "64")):
        flags[flags.index(flag) + 1] = value
    mix = copy.deepcopy(spec.traffic(mix_name))
    mix["concurrency"] = 4
    mix["prompt"].update(median=40, min=8, max=120)
    mix["output"].update(median=16, min=4, max=40)
    return conf, mix


def _stale(fn):
    def step(*a):
        logits, _ = fn(*a)
        return logits, a[2]
    return step


def _half_batch(fn):
    def step(*a):
        b = a[5].shape[0]
        valid = a[5].at[b // 2:].set(0)
        logits, cache = fn(*a[:5], valid)
        return logits.at[b // 2:].set(logits[:b - b // 2]), cache
    return step


def _token(fn):
    import jax.numpy as jnp
    count = [0]

    def step(*a):
        logits, cache = fn(*a)
        count[0] += 1
        if count[0] % 5 == 0:
            top = jnp.argmax(logits, axis=-1)
            nxt = (top + 1) % logits.shape[-1]
            rows = jnp.arange(logits.shape[0])
            logits = logits.at[rows, nxt].set(logits[rows, top] + 1.0)
        return logits, cache
    return step


FAULTS = {"sound": None, "control": None, "stale": _stale,
          "half_batch": _half_batch, "token": _token}


def run(config: str, mix_name: str, fault: str, seed: int = 2**33 + 5,
        seconds: float = 2.0) -> dict:
    conf, mix = small(config, mix_name)
    wrap = FAULTS[fault]

    def on_server(server):
        if wrap is not None:
            server._pstep = wrap(server._pstep)

    res = harness.run_cell(conf, mix, seed=seed, seconds=seconds,
                           trace=False, started=time.perf_counter(),
                           require_chip=False, control=fault == "control",
                           on_server=on_server)
    return {"fault": fault, "correct": res["correct"],
            "compared": res["compared"], "tokens": res["ctx"].tokens,
            "limit": conf["correct"]["logit_gap_max"]}


def main(argv):
    config, mix_name, *faults = argv
    for fault in faults:
        print(json.dumps(run(config, mix_name, fault)), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
