"""The fused bit-parallel CIM matmul (kernels/cim_mvm.py): every weight
matmul of a paged step, the head included.

Least work of one call of M rows, K inputs and N outputs: 2·M·K·N integer
operations (4-bit × 4-bit codes are exact in int8 MACs with int32 sums),
and K·N/2 bytes of weights at their stored nibble width, M·K bytes of
activation codes and 2·M·N bytes of bfloat16 outputs. K is the
algorithm's, not the 144-padded one. M counts useful rows only: the new
tokens the lanes carry (Σ valid) in the layers, and one row per lane that
carries any in the head; rows a step pads out to B·C are not work.
"""
PATTERN = r"cim_mvm"
PEAK = "int8_ops"


def shapes(m, c, lens, valid):
    rows = int(sum(int(v) for v in valid))
    hd = m.heads * m.dh
    kvd = m.kv_heads * m.dh
    layer = [(rows, m.d, hd), (rows, m.d, kvd), (rows, m.d, kvd),
             (rows, hd, m.d), (rows, m.d, m.d_ff), (rows, m.d, m.d_ff),
             (rows, m.d_ff, m.d)]
    lanes = sum(1 for v in valid if v > 0)
    return layer * m.layers + [(lanes, m.d, m.vocab)]


def calls(m, c, lens, valid):
    return [(2 * r * k * n, k * n // 2 + r * k + 2 * r * n)
            for r, k, n in shapes(m, c, lens, valid)]
