"""Work a round requires, counted from the cell's shapes.

Counts are of the arithmetic the algorithm needs, whatever implements
it: PRNG draws and elementwise work are not counted.

- local training: 3 x forward FLOPs (forward + two backward matmuls)
  for every sample of every local step: S * C * M * I * tau * batch;
- eval: one forward pass over the test set per seed;
- OTA matched filter (faithful channel only): the received-signal
  multiply-adds, one complex MAC (8 FLOPs) per receiver, transmitter,
  antenna and symbol: C * (C * M) * K * N per cluster hop and
  C * K_ps * N for the IS -> PS hop;
- hop bytes: the complex float32 symbols in (8 bytes each) and the
  combined symbols out.
"""
from __future__ import annotations

from bench.inputs import model

FLOP_PER_CMAC = 8
CX_BYTES = 8


def n_symbols(cfg: dict) -> int:
    """N: complex symbols per model update (half the even-padded
    parameter count)."""
    n = cfg["n_params"]
    return (n + n % 2) // 2


def train_flops(cfg: dict, seeds: int) -> int:
    users = cfg["C"] * cfg["M"]
    return (3 * model(cfg).forward_flops() * seeds * users * cfg["I"]
            * cfg["tau"] * cfg["batch"])


def eval_flops(cfg: dict, seeds: int) -> int:
    return model(cfg).forward_flops() * cfg["n_test"] * seeds


def hop_macs(cfg: dict, seeds: int) -> int:
    """Complex MACs of one round's matched filters, all seeds."""
    C, M, N = cfg["C"], cfg["M"], n_symbols(cfg)
    return seeds * (cfg["I"] * C * (C * M) * cfg["K"] * N
                    + C * cfg["K_ps"] * N)


def hop_bytes(cfg: dict, seeds: int) -> int:
    """Symbols into and out of one round's hops, all seeds."""
    C, M, N = cfg["C"], cfg["M"], n_symbols(cfg)
    return seeds * CX_BYTES * N * (cfg["I"] * (C * M + C) + C + 1)


def round_flops(cfg: dict, traffic: dict, seeds: int) -> int:
    total = train_flops(cfg, seeds) + eval_flops(cfg, seeds)
    if traffic["channel"] != "equivalent":
        total += FLOP_PER_CMAC * hop_macs(cfg, seeds)
    return total
