"""Fused OTA kernel: the least time the round's hops could take on the
chip over the kernel's measured time per round, in percent.  The least
time is the larger of the matched filter's FLOPs (8 per complex MAC)
over the bf16 peak and the symbols moved over HBM bandwidth; the FLOP
bound applies at these shapes."""
from bench import flops
from bench.harness import metric_reader


def read(ctx):
    ms = metric_reader("hop_kernel_ms")(ctx)
    peak_f = ctx.peaks.get("bf16_flops_per_s")
    peak_b = ctx.peaks.get("hbm_bytes_per_s")
    if ms is None or not peak_f or not peak_b:
        return None
    cfg, S = ctx.cell.config, ctx.seeds
    least_s = max(flops.FLOP_PER_CMAC * flops.hop_macs(cfg, S) / peak_f,
                  flops.hop_bytes(cfg, S) / peak_b)
    return 100.0 * least_s / (ms * 1e-3)
