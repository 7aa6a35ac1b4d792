"""Round program: the round's required FLOPs (bench/flops.py, from the
cell's shapes) times the traced window's rounds per second, over the
chips' bf16 peak (bench/peaks.json), in percent."""
from bench import flops


def read(ctx):
    peak = ctx.peaks.get("bf16_flops_per_s")
    if not peak or ctx.window.rounds == 0:
        return None
    per_round = flops.round_flops(ctx.cell.config, ctx.cell.traffic,
                                  ctx.seeds)
    rate = ctx.window.rounds / ctx.window.seconds
    return 100.0 * per_round * rate / (ctx.cell.chips * peak)
