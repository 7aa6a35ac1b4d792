"""Eval: device milliseconds per round in the program's `whfl.eval`
scope, the eval the chunk folds in once a window; self time averaged
over the chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.per_round_ms(ctx, "whfl.eval")
