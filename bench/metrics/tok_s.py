"""Prompt tokens advanced through prefill (those served from the prefix
cache included, each position once) plus output tokens emitted, over the
window's seconds; both counted from the benchmark's own records."""
UNIT = "tokens/s"


def read(ctx):
    return ctx.tokens / ctx.window_s
