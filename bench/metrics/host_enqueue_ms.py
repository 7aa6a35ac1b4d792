"""Sweep driver (host): milliseconds the host spends in the chunk call
per eval window — the harness's own perf_counter span around each
dispatch, summed over the window and divided by the windows."""


def read(ctx):
    if ctx.window.windows == 0:
        return None
    return 1e3 * ctx.window.enqueue_s / ctx.window.windows
