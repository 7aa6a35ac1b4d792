"""Local training: device milliseconds per round in the program's
`whfl.train` scope, the minibatch draw and gather (`whfl.batch`)
included; self time averaged over the chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.per_round_ms(ctx, "whfl.train", "whfl.batch")
