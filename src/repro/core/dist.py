"""Distributed W-HFL: hierarchical OTA aggregation on a device mesh (Mode B).

Maps the paper's protocol onto a TPU pod mesh:

    MU (mobile user)      -> one (pod, cluster, user) mesh coordinate
    cluster + IS          -> `user` sub-axis group; cluster hop = psum('user')
    PS, global OTA        -> psum(('pod','cluster')) — the pod-crossing hop
    OTA channel           -> second-order-matched "equivalent" channel
                             (validated against the faithful simulator in
                             tests/test_channel.py): per-user gain jitter
                             beta(1+eps)/beta_bar, interference + thermal
                             noise with the Lemma 7-14 variances.

The `data` axis of the production mesh is refined into (cluster, user)
sub-axes over the *identical* device order (see launch/mesh.py), so the
cluster hop is a cheap intra-pod grouped all-reduce and only the global
hop crosses the pod interconnect — exactly the paper's "aggregate often
over short links, rarely over the long one".

All functions here run INSIDE `jax.shard_map` with manual axes
``('pod','cluster','user')`` and auto (XLA SPMD) sharding over 'model'.

Noise is generated locally and identically on every member of a logical
receiver group (keys are folded with the receiver's coordinate only), so
channel emulation costs zero extra collective traffic.  Real/complex
bookkeeping: the paper packs R^{2N} into C^N; a CN(0,V) perturbation per
complex entry is V/2 per real component, which is what we apply to the
(real) parameter pytrees.
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.topology import Topology


@dataclass(frozen=True)
class DistGeom:
    """Per-user large-scale fading for the mesh-mapped W-HFL deployment.

    C total clusters (= n_pods * clusters_per_pod), M users each.
    """
    C: int
    M: int
    K: int                  # IS rx antennas
    K_ps: int               # PS rx antennas
    sigma_h2: float
    sigma_z2: float
    beta_own: np.ndarray    # [C, M]  MU -> own IS
    beta_cross: np.ndarray  # [C]     sum over other-cluster MU -> this IS
                            #         (inter-cluster interference weight)
    beta_is: np.ndarray     # [C]     IS -> PS

    @property
    def beta_bar_c(self) -> np.ndarray:  # [C]
        return self.beta_own.sum(axis=1)

    @property
    def beta_bar(self) -> float:
        return float(self.beta_is.sum())


def geom_from_topology(topo: Topology, n_pods: int = 1) -> DistGeom:
    """Tile a (C, M) radio topology across pods (each pod hosts an
    independent copy of the cluster geometry; the PS hop spans pods)."""
    b = np.asarray(topo.beta_mu_is, np.float64)
    b_own = np.stack([b[c, :, c] for c in range(topo.C)])
    b_cross = np.stack([
        sum(b[cp, :, c].sum() for cp in range(topo.C) if cp != c)
        for c in range(topo.C)])
    return DistGeom(
        C=topo.C * n_pods, M=topo.M, K=topo.K, K_ps=topo.K_ps,
        sigma_h2=topo.sigma_h2, sigma_z2=topo.sigma_z2,
        beta_own=np.tile(b_own, (n_pods, 1)),
        beta_cross=np.tile(b_cross, n_pods),
        beta_is=np.tile(np.asarray(topo.beta_is, np.float64), n_pods),
    )


def uniform_geom(C: int, M: int, K: int = 64, K_ps: int = 64,
                 sigma_h2: float = 1.0, sigma_z2: float = 1.0,
                 d_mu: float = 0.75, d_is: float = 1.75, d_cross: float = 2.5,
                 p: float = 4.0) -> DistGeom:
    return DistGeom(
        C=C, M=M, K=K, K_ps=K_ps, sigma_h2=sigma_h2, sigma_z2=sigma_z2,
        beta_own=np.full((C, M), d_mu ** (-p)),
        beta_cross=np.full((C,), (C - 1) * M * d_cross ** (-p)),
        beta_is=np.full((C,), d_is ** (-p)),
    )


@dataclass(frozen=True)
class OTADistConfig:
    mode: str = "equivalent"      # "equivalent" | "ideal"
    interference: bool = True
    per_element_interference: bool = True
    # per-element: faithful Lemma 7/9 per-entry interference variance
    # (costs a second grad-sized grouped psum per hop).  scalar: one
    # scalar psum — the power-matched homogenized approximation.
    fused: bool = False           # fold hops into one all-reduce (beyond-paper)
    # fused-FSDP path only: per-element mean-square of a typical user
    # delta, used for the interference variance (per-user powers are not
    # observable after the fused reduce).  None -> thermal noise only.
    tx_power_proxy: Optional[float] = None


# ---------------------------------------------------------------------------
# axis helpers (valid inside shard_map over ('pod','cluster','user'))
# ---------------------------------------------------------------------------

def cluster_id():
    """Global cluster index = pod * clusters_per_pod + cluster."""
    return (jax.lax.axis_index("pod") * jax.lax.axis_size("cluster")
            + jax.lax.axis_index("cluster"))


def user_id():
    return cluster_id() * jax.lax.axis_size("user") + jax.lax.axis_index("user")


def _noise_like(key, tree, std_tree_or_scalar):
    """Gaussian noise with per-leaf std (scalar or matching tree)."""
    leaves, treedef = jax.tree.flatten(tree)
    keys = jax.random.split(key, len(leaves))
    stds = (jax.tree.leaves(std_tree_or_scalar)
            if isinstance(std_tree_or_scalar, (dict, list, tuple))
            else [std_tree_or_scalar] * len(leaves))
    out = [jax.random.normal(k, l.shape, jnp.float32).astype(l.dtype)
           * jnp.asarray(s, l.dtype)
           for k, l, s in zip(keys, leaves, stds)]
    return jax.tree.unflatten(treedef, out)


def _tree_sqsum(tree):
    return sum(jnp.sum(jnp.square(l.astype(jnp.float32)))
               for l in jax.tree.leaves(tree))


def _tree_size(tree) -> int:
    return sum(int(np.prod(l.shape)) for l in jax.tree.leaves(tree))


# ---------------------------------------------------------------------------
# the two OTA hops
# ---------------------------------------------------------------------------

def cluster_hop(delta, geom: DistGeom, key, P_t, cfg: OTADistConfig):
    """MU -> IS OTA aggregation (eq. 8-13, equivalent channel).

    `delta` is this user's model-delta pytree (may be 'model'-sharded in
    auto land).  Returns the cluster estimate, identical on every member
    of the cluster.  Collectives: one psum('user') (+ one more when
    per_element_interference).
    """
    ci, ui = cluster_id(), jax.lax.axis_index("user")
    beta_own = jnp.asarray(geom.beta_own, jnp.float32)        # [C, M]
    b_m = beta_own[ci, ui]
    bb_c = jnp.asarray(geom.beta_bar_c, jnp.float32)[ci]

    if cfg.mode == "ideal":
        mean = jax.tree.map(
            lambda x: jax.lax.psum(x / geom.M, "user"), delta)
        return mean

    # per-user effective gain: (beta_m / bbar_c) * (1 + eps), eps~N(0,1/K)
    k_eps = jax.random.fold_in(key, user_id())
    eps = _noise_like(k_eps, delta, 1.0 / np.sqrt(geom.K))
    w = b_m / bb_c
    weighted = jax.tree.map(
        lambda x, e: (x.astype(jnp.float32) * (1.0 + e.astype(jnp.float32))
                      * w).astype(x.dtype), delta, eps)
    est = jax.tree.map(lambda x: jax.lax.psum(x, "user"), weighted)

    # thermal noise (per real element: V/2, V = Lemma-9 complex variance)
    v_th = geom.sigma_z2 / (geom.K * (P_t ** 2) * geom.sigma_h2 * bb_c) / 2.0
    v_base = jnp.asarray(v_th, jnp.float32)

    if cfg.interference:
        # inter-cluster term: other clusters' aggregate tx power, scalar
        # surrogate using this cluster's mean tx power (symmetric layout).
        bc = jnp.asarray(geom.beta_cross, jnp.float32)[ci]
        pw_own = jax.lax.psum(_tree_sqsum(delta) / geom.M, "user")
        v_base = v_base + (bc * pw_own / float(max(_tree_size(delta), 1))
                           / (geom.K * bb_c ** 2)) / 2.0
        wi = b_m * (bb_c - b_m) / (geom.K * bb_c ** 2)
        if cfg.per_element_interference:
            # per-element Lemma 7 variance: sum_m' b_m'(bb-b_m')|D|^2/(K bb^2)
            p2 = jax.tree.map(
                lambda x: jax.lax.psum(
                    wi * jnp.square(x.astype(jnp.float32)), "user"), delta)
            std = jax.tree.map(lambda v: jnp.sqrt(v / 2.0 + v_base), p2)
        else:
            # scalar power-matched approximation: one scalar psum
            pw = jax.lax.psum(wi * _tree_sqsum(delta), "user")
            std = jnp.sqrt(pw / float(max(_tree_size(delta), 1)) / 2.0 + v_base)
    else:
        std = jnp.sqrt(v_base)

    # identical noise on every member: key folded with the CLUSTER id
    k_no = jax.random.fold_in(key, 1_000_003 + ci)
    noise = _noise_like(k_no, est, std)
    return jax.tree.map(lambda a, n: a + n.astype(a.dtype), est, noise)


def global_hop(is_delta, geom: DistGeom, key, P_is_t, cfg: OTADistConfig):
    """IS -> PS OTA aggregation (eq. 15-18, equivalent channel).

    `is_delta` is the cluster's accumulated delta (identical over the
    cluster's members).  psum over ('pod','cluster') at a fixed user
    coordinate sums each cluster exactly once.
    """
    ci = cluster_id()
    b_is = jnp.asarray(geom.beta_is, jnp.float32)
    bb = jnp.asarray(geom.beta_bar, jnp.float32)

    if cfg.mode == "ideal":
        return jax.tree.map(
            lambda x: jax.lax.psum(x / geom.C, ("pod", "cluster")), is_delta)

    k_eps = jax.random.fold_in(key, 2_000_003 + ci)
    eps = _noise_like(k_eps, is_delta, 1.0 / np.sqrt(geom.K_ps))
    w = b_is[ci] / bb
    weighted = jax.tree.map(
        lambda x, e: (x.astype(jnp.float32) * (1.0 + e.astype(jnp.float32))
                      * w).astype(x.dtype), is_delta, eps)
    est = jax.tree.map(
        lambda x: jax.lax.psum(x, ("pod", "cluster")), weighted)

    v_th = geom.sigma_z2 / (geom.K_ps * (P_is_t ** 2) * geom.sigma_h2 * bb) / 2.0
    if cfg.interference and geom.C > 1:
        wi = b_is[ci] * (bb - b_is[ci]) / (geom.K_ps * bb ** 2)
        if cfg.per_element_interference:
            p2 = jax.tree.map(
                lambda x: jax.lax.psum(
                    wi * jnp.square(x.astype(jnp.float32)),
                    ("pod", "cluster")), is_delta)
            std = jax.tree.map(lambda v: jnp.sqrt(v / 2.0 + v_th), p2)
        else:
            pw = jax.lax.psum(wi * _tree_sqsum(is_delta), ("pod", "cluster"))
            std = jnp.sqrt(pw / float(max(_tree_size(is_delta), 1)) / 2.0 + v_th)
    else:
        std = jnp.sqrt(jnp.asarray(v_th, jnp.float32))

    k_no = jax.random.fold_in(key, 3_000_017)  # one PS: same key everywhere
    noise = _noise_like(k_no, est, std)
    return jax.tree.map(lambda a, n: a + n.astype(a.dtype), est, noise)


def fused_whfl_aggregate(delta, geom: DistGeom, key, P_t, P_is_t,
                         cfg: OTADistConfig):
    """Beyond-paper fused path: both hops in ONE all-reduce.

    The two-hop composition (tau=1, I=1) is

        est = sum_c wg_c (1+eps_c) [ sum_m wc_m (1+eps_m) D_m + n_c ] + n_g

    With per-user scalar jitter the weights fold into a single per-user
    scalar, the cluster-noise contribution sum_c wg_c n_c is generated
    locally (identical on every device), and the whole aggregation is one
    flat psum over ('pod','cluster','user') — XLA already reduces that
    hierarchically over the mesh.  ~2-3x less collective traffic than the
    structural path with per-element interference, identical first/second
    moments up to per-element vs per-user jitter granularity.
    """
    ci = cluster_id()
    beta_own = jnp.asarray(geom.beta_own, jnp.float32)
    b_m = beta_own[ci, jax.lax.axis_index("user")]
    bb_c = jnp.asarray(geom.beta_bar_c, jnp.float32)[ci]
    b_is = jnp.asarray(geom.beta_is, jnp.float32)
    bb = jnp.asarray(geom.beta_bar, jnp.float32)

    if cfg.mode == "ideal":
        return jax.tree.map(
            lambda x: jax.lax.psum(x / (geom.C * geom.M),
                                   ("pod", "cluster", "user")), delta)

    # scalar per-user and per-cluster gain jitter
    k_u = jax.random.fold_in(key, user_id())
    k_c = jax.random.fold_in(key, 2_000_003 + ci)
    eps_m = jax.random.normal(k_u, ()) / np.sqrt(geom.K)
    eps_c = jax.random.normal(k_c, ()) / np.sqrt(geom.K_ps)
    w = (b_m / bb_c) * (1.0 + eps_m) * (b_is[ci] / bb) * (1.0 + eps_c)
    est = jax.tree.map(
        lambda x: jax.lax.psum((x.astype(jnp.float32) * w).astype(x.dtype),
                               ("pod", "cluster", "user")), delta)

    # channel noise, all generated locally:
    #   sum_c (wg_c)^2 * V_cluster(c)  +  V_global
    pw = jax.lax.psum(_tree_sqsum(delta) / (geom.C * geom.M),
                      ("pod", "cluster", "user"))  # avg per-user tx power
    n_el = max(_tree_size(delta), 1)
    bo = jnp.asarray(geom.beta_own, jnp.float32)
    bbc = jnp.asarray(geom.beta_bar_c, jnp.float32)
    v_c = (jnp.sum(bo * (bbc[:, None] - bo), axis=1) * (pw / float(n_el))
           / (geom.K * bbc ** 2)
           + jnp.asarray(geom.beta_cross, jnp.float32) * geom.M * (pw / float(n_el))
           / (geom.K * bbc ** 2)
           + geom.sigma_z2 / (geom.K * (P_t ** 2) * geom.sigma_h2 * bbc))
    wg2 = (b_is / bb) ** 2
    v_cluster_tot = jnp.sum(wg2 * v_c)
    v_glob = (jnp.sum(b_is * (bb - b_is)) * (pw / float(n_el)) / (geom.K_ps * bb ** 2)
              + geom.sigma_z2 / (geom.K_ps * (P_is_t ** 2)
                                 * geom.sigma_h2 * bb))
    std = jnp.sqrt((v_cluster_tot + v_glob) / 2.0)
    k_no = jax.random.fold_in(key, 3_000_017)
    noise = _noise_like(k_no, est, std)
    return jax.tree.map(lambda a, n: a + n.astype(a.dtype), est, noise)


def whfl_aggregate(delta, geom: DistGeom, key, P_t, P_is_t,
                   cfg: OTADistConfig):
    """One W-HFL aggregation round (tau=1, I=1 composition) of a delta
    pytree.  Structural (two-hop) or fused depending on cfg.fused."""
    if cfg.fused:
        return fused_whfl_aggregate(delta, geom, key, P_t, P_is_t, cfg)
    k1, k2 = jax.random.split(key)
    est_c = cluster_hop(delta, geom, k1, P_t, cfg)
    return global_hop(est_c, geom, k2, P_is_t, cfg)
