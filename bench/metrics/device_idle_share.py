"""Device: the share of the traced window in which no operation ran on
the chips (1 - busy union / window), averaged over the chips, in
percent."""
from bench import trace


def read(ctx):
    if not ctx.events:
        return None
    lo, hi = trace.window(ctx.events)
    chips = [str(c) for c in range(ctx.cell.chips)]
    busy = sum(trace.busy_ns(ctx.events, c) for c in chips) / len(chips)
    return 100.0 * (1.0 - busy / (hi - lo))
