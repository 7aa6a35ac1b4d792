"""Per-round in-program diagnostics (the paper's physical-layer view).

The convergence bound of arXiv:2207.09232 is written in quantities the
trainer never surfaced: path-loss-weighted receive power at each IS,
the effective post-matched-filter noise variance, the second moment of
the aggregated update.  This module computes them *inside* the round
function — the one round body both engines run
(`repro.core.whfl.make_round_body`) calls in with values it
already materializes (flat per-user deltas, fold outputs, participation
masks), so telemetry adds no extra hop and no host sync; the chunked
drivers carry the block through the scan and fetch it with the
round metrics in the same single `device_get`.

Field glossary (paper symbols; all float32, shapes `()` or `[C]`):

- ``attendance`` — realized fraction of MUs transmitting this round
  (``mean`` of the participation mask; exactly 1 under the paper's
  full-attendance assumption).
- ``symbol_energy_edge`` — per-cluster mean per-symbol transmit energy
  of the MU -> IS hop, ``P_t^2 mean_m ||Delta_{c,m}||^2 / N`` (the
  per-cluster restriction of the reported average symbol power).
- ``rx_power`` — matched-filter receive signal power at IS c,
  ``P_t^2 sum_m beta_{c,m,c} ||Delta_{c,m}||^2 / N``.
- ``snr`` — ``rx_power / sigma_z^2``: the per-cluster-hop receive SNR
  (Scalable Hierarchical OTA-FL's per-tier design knob).
- ``noise_floor`` — effective per-entry noise variance of the cluster
  estimate after matched filtering and normalization,
  ``sigma_z^2 / (P_t^2 sigma_h^2 beta_bar_c K)`` — exactly the
  ``V_noise`` term of the `equivalent` channel backend
  (`repro.core.channel`).
- ``grad_norm_pre`` — ``||mean_m Delta_{c,m}||_2``: the norm of the
  ideal (noiseless, full-attendance) cluster mean.
- ``grad_norm_post`` — ``||est_c||_2``: the norm of the realized
  cluster-hop estimate (the per-cluster update norm).
- ``grad_ratio`` — ``grad_norm_post / grad_norm_pre`` (0 where the
  pre-norm is 0): the OTA distortion of the update magnitude, the
  quantity COTAF-style precoder monitoring tracks.
- ``symbol_energy_is`` / ``snr_is`` — the same per-symbol energy and
  receive SNR for the IS -> PS hop (zero in conventional mode, which
  has no second hop).

Conventional (single-hop) mode reuses the ``[C]`` layout: the per-MU
sums run against the PS geometry (``beta_mu_ps``, ``K_ps``), and the
scalar PS-side quantities (``noise_floor``, ``grad_norm_post``) are
broadcast over clusters.

Inputs are routed through `repro.core.aggregation.fence`
(`optimization_barrier`): telemetry consumers read a barrier-isolated
copy, so the original round subgraphs keep their fusion neighborhoods
and the ``telemetry=True`` program never perturbs model state or
metrics (the x+0 discipline, pinned by tests/test_obs.py).
"""
from __future__ import annotations

from typing import Dict, Optional

import jax.numpy as jnp
import numpy as np

from repro.core import aggregation as agg
from repro.core.topology import Topology

TELEMETRY_KEYS = (
    "attendance", "symbol_energy_edge", "rx_power", "snr",
    "noise_floor", "grad_norm_pre", "grad_norm_post", "grad_ratio",
    "symbol_energy_is", "snr_is",
)
EDGE_KEYS = TELEMETRY_KEYS[:8]
IS_KEYS = TELEMETRY_KEYS[8:]

_f32 = jnp.float32


def edge_telemetry_init(C: int) -> Dict[str, jnp.ndarray]:
    """Zero cluster-hop block — the scan-carry initializer matching
    `cluster_telemetry`'s structure (shape AND dtype, so the carry
    avals line up)."""
    z = jnp.zeros((), _f32)
    zc = jnp.zeros((C,), _f32)
    return {"attendance": z, "symbol_energy_edge": zc, "rx_power": zc,
            "snr": zc, "noise_floor": zc, "grad_norm_pre": zc,
            "grad_norm_post": zc, "grad_ratio": zc}


def is_telemetry_zero() -> Dict[str, jnp.ndarray]:
    """Zero IS -> PS block (also the conventional mode's value: a
    single-hop round has no second hop to measure)."""
    z = jnp.zeros((), _f32)
    return {"symbol_energy_is": z, "snr_is": z}


def telemetry_init(C: int) -> Dict[str, jnp.ndarray]:
    """The full zero telemetry block `init_round_state` seeds the
    trainer state with (overwritten by the first round)."""
    return {**edge_telemetry_init(C), **is_telemetry_zero()}


def cluster_telemetry(flat, est, claimed, topo: Topology, P_t,
                      mode: str = "whfl") -> Dict[str, jnp.ndarray]:
    """Cluster-hop diagnostics from one round's materialized values.

    flat: per-user flat deltas ``[C, M, 2N]`` *after* any COTAF
    precoding (so energies match what was actually transmitted);
    est: the realized fold output (``[C, 2N]``, or the global ``[2N]``
    estimate in ``mode="conventional"``); claimed: the round's
    attendance mask ``[C, M]`` or None for full attendance.
    """
    C, M, two_n = flat.shape
    N = two_n // 2
    flat, est, P = agg.fence((flat, est, jnp.asarray(P_t, _f32)))
    E = jnp.sum(jnp.square(flat), axis=-1)                    # [C, M]
    if mode == "conventional":
        beta = jnp.asarray(np.asarray(topo.beta_mu_ps), _f32)
        bb = _f32(np.asarray(topo.beta_mu_ps).sum())
        K = float(topo.K_ps)
        post = jnp.broadcast_to(
            jnp.sqrt(jnp.sum(jnp.square(est), axis=-1)), (C,))
    else:
        beta = jnp.asarray(np.asarray(topo.beta_own), _f32)
        bb = jnp.asarray(np.asarray(topo.beta_bar_c), _f32)   # [C]
        K = float(topo.K)
        post = jnp.sqrt(jnp.sum(jnp.square(est), axis=-1))    # [C]
    rx = (P ** 2) * jnp.sum(beta * E, axis=-1) / N            # [C]
    nf = jnp.broadcast_to(
        _f32(topo.sigma_z2) / ((P ** 2) * _f32(topo.sigma_h2) * bb * K),
        (C,))
    pre = jnp.sqrt(jnp.sum(jnp.square(jnp.mean(flat, axis=1)), axis=-1))
    att = (jnp.mean(claimed) if claimed is not None
           else jnp.ones((), _f32))
    return {
        "attendance": jnp.asarray(att, _f32),
        "symbol_energy_edge": jnp.asarray(
            (P ** 2) * jnp.mean(E, axis=-1) / N, _f32),
        "rx_power": jnp.asarray(rx, _f32),
        "snr": jnp.asarray(rx / _f32(topo.sigma_z2), _f32),
        "noise_floor": jnp.asarray(nf, _f32),
        "grad_norm_pre": jnp.asarray(pre, _f32),
        "grad_norm_post": jnp.asarray(post, _f32),
        "grad_ratio": jnp.asarray(
            jnp.where(pre > 0, post / jnp.where(pre > 0, pre, 1.0), 0.0),
            _f32),
    }


def is_telemetry(is_deltas, topo: Topology, P_is_t) -> Dict[str, jnp.ndarray]:
    """IS -> PS hop diagnostics: per-symbol transmit energy and receive
    SNR from the accumulated IS deltas ``[C, 2N]``."""
    _, two_n = is_deltas.shape
    N = two_n // 2
    d, P = agg.fence((is_deltas, jnp.asarray(P_is_t, _f32)))
    E = jnp.sum(jnp.square(d), axis=-1)                       # [C]
    beta = jnp.asarray(np.asarray(topo.beta_is), _f32)
    return {
        "symbol_energy_is": jnp.asarray((P ** 2) * jnp.mean(E) / N, _f32),
        "snr_is": jnp.asarray(
            (P ** 2) * jnp.sum(beta * E) / (N * _f32(topo.sigma_z2)),
            _f32),
    }


def summarize(tele: Dict, claimed_only: Optional[tuple] = None) -> Dict:
    """Scalar (mean-over-everything) view of one telemetry block —
    what the trace journal emits per eval window."""
    keys = claimed_only if claimed_only is not None else TELEMETRY_KEYS
    return {k: float(np.mean(np.asarray(tele[k]))) for k in keys
            if k in tele}
