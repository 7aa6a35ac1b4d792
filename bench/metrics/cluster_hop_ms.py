"""Cluster hop: device milliseconds per round in the program's
`whfl.cluster_hop` scope, the MU->IS fold with its own glue (the fused
kernel where the cell runs it); self time averaged over the chips
(bench/scopes.py)."""
from bench import scopes


def read(ctx):
    return scopes.per_round_ms(ctx, "whfl.cluster_hop")
