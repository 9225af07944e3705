"""Device time per traced step of the layer scan's copies of the stacked
KV pool: the ops under the program's named scope `layer_scan` outside its
`attn` and `mlp` scopes (each layer's pool sliced out of the stack and
written back), found by their HLO op_name in the trace."""
from benchlib import spans

UNIT = "ms"


def read(ctx):
    t = spans.loaded(ctx)
    if t is None:
        return None
    return spans.scope_ms_per_step(t, spans.POOL_COPY,
                                   len(ctx.traced_steps))
