"""Pytree <-> flat-vector plumbing and power accounting for OTA hops.

The OTA channel operates on flat R^{2N} vectors (eq. 7 packing).  These
helpers ravel arbitrary model pytrees into padded even-length vectors
(vmap-safe, shapes fixed at trace time) and account transmit power the
way the paper reports it (average per-symbol power at the edge).
"""
from __future__ import annotations

from dataclasses import dataclass
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

def fence(tree):
    """`jax.lax.optimization_barrier`: keeps XLA from fusing the fenced
    values with their neighbours.  Under the sweep engine's ``vmap``
    seed-batch mode it fences the whole batched value."""
    return jax.lax.optimization_barrier(tree)


@dataclass(frozen=True)
class FlatSpec:
    shapes: Tuple[Tuple[int, ...], ...]
    sizes: Tuple[int, ...]
    treedef: object
    dtypes: Tuple[object, ...]
    two_n: int  # padded to even

    @property
    def n_params(self) -> int:
        return int(sum(self.sizes))


def make_flat_spec(tree) -> FlatSpec:
    leaves, treedef = jax.tree.flatten(tree)
    shapes = tuple(tuple(l.shape) for l in leaves)
    sizes = tuple(int(np.prod(s)) if s else 1 for s in shapes)
    total = int(sum(sizes))
    two_n = total + (total % 2)
    return FlatSpec(shapes=shapes, sizes=sizes, treedef=treedef,
                    dtypes=tuple(l.dtype for l in leaves), two_n=two_n)


def flatten(spec: FlatSpec, tree) -> jax.Array:
    """tree -> [2N] float32 (zero-padded to even length). vmap-safe."""
    leaves = jax.tree.leaves(tree)
    flat = jnp.concatenate([l.reshape(-1).astype(jnp.float32)
                            for l in leaves])
    pad = spec.two_n - flat.shape[-1]
    if pad:
        flat = jnp.concatenate([flat, jnp.zeros((pad,), jnp.float32)])
    return flat


def unflatten(spec: FlatSpec, vec: jax.Array):
    """[2N] -> tree (padding dropped)."""
    out: List[jax.Array] = []
    off = 0
    for shape, size, dt in zip(spec.shapes, spec.sizes, spec.dtypes):
        out.append(vec[off:off + size].reshape(shape).astype(dt))
        off += size
    return jax.tree.unflatten(spec.treedef, out)


def user_energy(flat: jax.Array) -> jax.Array:
    """Per-transmission symbol energy ``sum(flat^2)`` over the last
    axis ([..., 2N] -> [...]).

    Both execution engines derive the power metrics through this exact
    helper (the sharded executor calls it per user inside a
    ``lax.map``, the single engine batched over [C, M]) and the
    `optimization_barrier` fences keep the reduction out of
    engine-specific fusion neighborhoods, so the two programs fold the
    same subgraph.  The alignment is bitwise for the paper scenarios
    (pinned in tests/test_uneven_mesh.py); XLA:CPU layout assignment
    can still reorder the accumulation for some odd shapes, which the
    cross-engine tests bound at <= 1 ULP on the power scalars (model
    state stays bitwise everywhere)."""
    return fence(jnp.sum(jnp.square(fence(flat)), axis=-1))


def symbol_power_from_energy(pw: jax.Array, P, n: int) -> jax.Array:
    """Fold per-transmission energies ([...], from `user_energy`) into
    the paper's reported average per-symbol power
    ``mean(P^2 * pw / n)``, fenced exactly like `user_energy` so every
    engine folds the identical subgraph."""
    pw, P = fence((jnp.asarray(pw), jnp.asarray(P)))
    return fence(jnp.mean((P ** 2) * pw / n))


# ---------------------------------------------------------------------------
# partial participation: COTAF-style precoding + attendance rescale
# ---------------------------------------------------------------------------

def cotaf_precode(flat: jax.Array, scale: jax.Array) -> jax.Array:
    """Per-user transmit precoding: ``flat [..., C, M, 2N] * scale
    [..., C, M]`` broadcast over the symbol axis.

    A sampled-out user gets scale 0 — its transmission *is* the
    inactive pad slot of `repro.core.topology.PadPlan`, drawn per round
    — a free rider 0, a byzantine user ``-byzantine_scale``, an honest
    one 1.  Scaling happens before any hop AND before the power fold,
    so both execution engines square/sum bitwise-identical symbol
    values (dropped users contribute exactly zero energy)."""
    return flat * scale[..., None]


def attendance_rescale(weights, claimed: jax.Array,
                       axis: int = -1) -> jax.Array:
    """COTAF-style time-varying renormalization for the realized
    attendance (Sery et al.: the precoding factor follows the active
    set, so the estimate stays unbiased under partial participation).

    The OTA backends normalize by the *full* receive-weight sum
    (``beta_bar_c`` for the faithful/equivalent folds, the user count
    for the ideal mean).  With only the `claimed` users transmitting,
    the matched-filter mean is over the claimed weight sum instead —
    this returns the per-cluster correction ``full_sum / claimed_sum``
    (exactly 1.0 at full attendance, 0 where nobody claimed so an
    empty cluster contributes no update rather than amplified noise).

    weights: static receive weights, e.g. ``topo.beta_own [C, M]``
    (ones for ``mode="ideal"``); claimed: {0,1} mask, same shape.
    """
    w = jnp.asarray(weights, jnp.float32)
    full = jnp.sum(w, axis=axis)
    got = jnp.sum(w * claimed, axis=axis)
    return jnp.where(got > 0, full / jnp.where(got > 0, got, 1.0), 0.0)


# ---------------------------------------------------------------------------
# robust cluster folds (masked coordinate statistics a la COMED)
# ---------------------------------------------------------------------------

def masked_median(x: jax.Array, mask: jax.Array) -> jax.Array:
    """Coordinate-wise median over the claimed users of each cluster.

    x: per-user estimates ``[C, M, 2N]``; mask: {0,1} ``[C, M]`` —
    unclaimed users are excluded from the order statistic (sorted to
    the +inf tail), and the median index follows the *realized*
    attendance count, so the fold is exact for any per-round mask.
    Clusters with no claimed user return 0 (no update)."""
    xs = jnp.sort(jnp.where(mask[..., None] > 0, x, jnp.inf), axis=1)
    n = jnp.sum(mask > 0, axis=1).astype(jnp.int32)            # [C]
    lo = jnp.maximum((n - 1) // 2, 0)
    hi = n // 2

    def take(idx):
        return jnp.take_along_axis(xs, idx[:, None, None], axis=1)[:, 0]

    med = 0.5 * (take(lo) + take(hi))
    return jnp.where((n > 0)[:, None], med, 0.0)


def masked_trimmed_mean(x: jax.Array, mask: jax.Array,
                        trim: float = 0.25) -> jax.Array:
    """Coordinate-wise trimmed mean over the claimed users of each
    cluster: per coordinate, drop the ``floor(trim * n)`` smallest and
    largest claimed values and average the rest (``trim < 0.5``).  The
    trim count follows the realized attendance ``n``, clusters with no
    claimed user return 0."""
    if not 0.0 <= trim < 0.5:
        raise ValueError(f"trim must be in [0, 0.5), got {trim}")
    M = x.shape[1]
    xs = jnp.sort(jnp.where(mask[..., None] > 0, x, jnp.inf), axis=1)
    n = jnp.sum(mask > 0, axis=1).astype(jnp.int32)[:, None]    # [C, 1]
    k = jnp.floor(np.float32(trim) * n.astype(jnp.float32)).astype(jnp.int32)
    ranks = jnp.arange(M, dtype=jnp.int32)[None, :]
    keep = (ranks >= k) & (ranks < n - k)                       # [C, M]
    kept = jnp.where(keep[..., None], xs, 0.0)
    cnt = jnp.maximum(n - 2 * k, 1).astype(jnp.float32)
    return jnp.where(n > 0, jnp.sum(kept, axis=1) / cnt, 0.0)


def symbol_power(flat: jax.Array, P) -> jax.Array:
    """Average transmit power per complex symbol for one transmission of
    the packed vector `flat` ([..., 2N]) with power multiplier P:
    P^2 * E_n |Delta^cx_n|^2 = P^2 * sum(flat^2)/N, averaged over
    leading axes (users).  Composed from the shared `user_energy` /
    `symbol_power_from_energy` pair (see their fencing notes)."""
    two_n = flat.shape[-1]
    return symbol_power_from_energy(user_energy(flat), P, two_n // 2)
