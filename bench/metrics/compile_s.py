"""Seconds the XLA backend spent compiling during set-up, from JAX's
`backend_compile_duration` events (programs read from the persistent cache
do not compile, so a warm run reads near 0)."""

LAYER = "set-up: compiler"
UNIT = "s"
MOVES = "setup_s"


def read(ctx):
    return ctx.setup["compile_s"]
