"""Local training's minibatch: device milliseconds per round in the
program's `whfl.batch` scope, the index draw and the gather of the
batch from the user's shard; self time averaged over the chips
(bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.per_round_ms(ctx, "whfl.batch")
