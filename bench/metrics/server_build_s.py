"""Host clock around `Server(params, cfg, serving)`: the program's eager
offline weight quantization and packing, and the KV pool."""
UNIT = "s"


def read(ctx):
    return ctx.server_build_s
