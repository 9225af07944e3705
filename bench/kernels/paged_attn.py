"""Paged flash attention (kernels/paged_attention.py), one call per layer
over every lane of a step.

Least work of a call, over the lanes that carry tokens: 4·H·dh operations
per (query, live key) pair, with causal keys (a lane with `lens` cached
tokens and `valid` new ones has Σ_i (lens + i + 1) pairs), and the bytes
of the live positions' bfloat16 K and V plus bfloat16 q and o.
"""
PATTERN = r"paged_attn"
PEAK = "bf16_flops"


def calls(m, c, lens, valid):
    pairs = kv = qo = 0
    for n, v in zip(lens, valid):
        n, v = int(n), int(v)
        if v <= 0:
            continue
        pairs += v * n + v * (v + 1) // 2
        kv += n + v
        qo += v
    flops = 4 * m.heads * m.dh * pairs
    nbytes = 2 * 2 * kv * m.kv_heads * m.dh + 2 * 2 * qo * m.heads * m.dh
    return [(flops, nbytes)] * m.layers
