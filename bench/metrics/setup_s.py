"""Process start to the end of warm-up: imports, pool, LM head, prefix, compiles."""

UNIT = "s"
SOURCE = "host_clock"


def read(ctx):
    return ctx.setup_s
