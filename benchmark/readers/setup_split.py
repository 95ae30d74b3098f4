"""Set-up split in two: seconds jax spent tracing, lowering and
compiling (jax.monitoring's compile events, their union), and the rest
of set-up on the host."""


def read(ctx, part):
    compile_s = ctx["compile_s"]
    return compile_s if part == "compile" else ctx["setup_s"] - compile_s
