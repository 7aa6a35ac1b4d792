"""Round program build: seconds JAX spent tracing, lowering and
compiling during set-up (its monitoring events), cache loads excluded."""


def read(ctx):
    return ctx.compile_s
