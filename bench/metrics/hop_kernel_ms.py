"""Fused OTA kernel: device milliseconds per round of the operations
the trace names after the kernel, summed over the chips.  The kernel's
`pallas_call` has no name of its own; a TPU trace names its custom call
after the jitted `fused_mac` that holds it ("fused_mac.25")."""
from bench import trace

KERNEL = r"^fused_mac(\.\d+)?$"


def read(ctx):
    if not ctx.events or ctx.window.rounds == 0:
        return None
    ns = trace.kernel_ns(ctx.events, KERNEL)
    if ns <= 0:
        return None
    return 1e-6 * ns / ctx.window.rounds
