"""Model FLOPs of the traced steps' useful work over the traced window's
seconds and the chip's highest peak (int8): 2 x matmul weights per token
and per emitted logit row, plus attention at each token's live length."""
UNIT = "%"


def _flops(m, lens, valid):
    tokens = int(valid.sum())
    lanes = int((valid > 0).sum())
    head = m.d * m.vocab
    attn = sum(int(v) * int(n) + int(v) * (int(v) + 1) // 2
               for n, v in zip(lens, valid))
    return 2 * (m.matmul_params() - head) * tokens + 2 * head * lanes \
        + 4 * m.heads * m.dh * attn * m.layers


def read(ctx):
    t = ctx.trace
    if t is None:
        return None
    flops = sum(_flops(ctx.model, call.lens, call.valid)
                for s in ctx.traced_steps for call in s.calls)
    return 100.0 * flops / (t["window_s"] * ctx.peaks["int8_ops"])
