"""PS hop: device milliseconds per round in the program's
`whfl.ps_hop` scope, the IS->PS hop of W-HFL (MU->PS in conventional
mode); self time averaged over the chips (bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.per_round_ms(ctx, "whfl.ps_hop")
