"""CNN convolutions: the FLOPs a round's convolutions require
(`conv_round_flops` of the cell's reference model) over their measured
device time per round (`conv_ms`) and the chips' bf16 peak
(bench/peaks.json), in percent."""
from bench.harness import metric_reader
from bench.inputs import model


def read(ctx):
    ms = metric_reader("conv_ms")(ctx)
    peak = ctx.peaks.get("bf16_flops_per_s")
    if not ms or not peak:
        return None
    flop = model(ctx.cell.config).conv_round_flops(ctx.cell.config,
                                                   ctx.seeds)
    return 100.0 * flop / (ms * 1e-3 * ctx.cell.chips * peak)
