"""Jit'd public wrappers for the OTA kernels.

These are the compute cores `repro.core.channel`'s backends call:

- `mf_combine` — slab path (``backend="slab_kernel"``): consumes the
  materialized complex channel/symbol/noise tensors, runs the planar
  Pallas kernel (interpret mode on CPU hosts, compiled on TPU) and
  returns the combined complex vector of eq. (9)/(16).  Accepts a
  single rx station (h ``[U,K,N]``) or a batch (h ``[B,U,K,N]``, one
  grid dispatch for all rx stations).
- `fused_combine` — fused path (``backend="fused"``): no channel
  tensors at all; the kernel derives fading and noise on the fly from
  a counter-based seed (see `repro.kernels.fused_mac`), so channel
  memory is O(block) instead of O(U*K*N).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.kernels.fused_mac import fused_mac
from repro.kernels.ota_combine import ota_combine, ota_combine_batched
from repro.kernels.ref import ota_combine_ref, ota_combine_ref_batched


def interpret_mode() -> bool:
    """How the Pallas kernels run on the default backend: compiled by
    Mosaic on a TPU (False), interpreted on the CPU (True — the tests).
    Any other backend is an error, never a silent interpreter run."""
    backend = jax.default_backend()
    if backend not in ("tpu", "cpu"):
        raise RuntimeError(
            f"the Pallas kernels run compiled on 'tpu' or interpreted on "
            f"'cpu'; the default backend is {backend!r}")
    return backend == "cpu"


def mf_combine(h: jax.Array, t: jax.Array, z: jax.Array,
               w: jax.Array | None = None, *, use_kernel: bool = True,
               block_n: int = 512, block_k: int = 8) -> jax.Array:
    """y[n] = sum_k conj(sum_u w_u h[u,k,n]) (sum_u h[u,k,n] t[u,n] + z[k,n]).

    h: complex64 [U, K, N] (or [B, U, K, N] for B rx stations sharing
    the transmit symbols); t: complex64 [U, N]; z: complex64 [K, N]
    (or [B, K, N]); w: float32 [U] (or [B, U]) matched-filter weights
    (default: all ones).  Returns complex64 [N] (or [B, N]).
    """
    batched = h.ndim == 4
    U = h.shape[1] if batched else h.shape[0]
    if w is None:
        w = (jnp.ones((h.shape[0], U), jnp.float32) if batched
             else jnp.ones((U,), jnp.float32))
    args = (jnp.real(h), jnp.imag(h), jnp.real(t), jnp.imag(t),
            jnp.real(z), jnp.imag(z), w)
    if use_kernel:
        fn = ota_combine_batched if batched else ota_combine
        y_re, y_im = fn(*args, block_n=block_n, block_k=block_k,
                        interpret=interpret_mode())
    else:
        fn = ota_combine_ref_batched if batched else ota_combine_ref
        y_re, y_im = fn(*args)
    return jax.lax.complex(y_re, y_im)


def fused_combine(seed: jax.Array, t: jax.Array, amp: jax.Array,
                  w: jax.Array, *, K: int, sigma_h2: float,
                  sigma_z2: float, rx_base=None, n_base=None,
                  u_base=None, block_n: int = 512, block_k: int = 8,
                  block_u: int = 32) -> jax.Array:
    """Fused combine over on-the-fly channels (no [U,K,N] slab).

    seed: uint32 [2] counter-PRNG seed words; t: complex64 [U, N]
    transmit symbols (pre-scaled by P); amp: float32 [B, U] channel
    amplitudes (sqrt of large-scale fading per rx station); w: float32
    [B, U] matched-filter weights.  Returns complex64 [B, N] — the
    un-rescaled eq. (9)/(16) combine per rx station.

    `rx_base`/`u_base`/`n_base` are the global counter bases of this
    call's (rx, u, n) tile (see `repro.kernels.fused_mac`): sharded
    callers pass their tile origin so every shard draws the channels
    of its global indices, bitwise independent of the mesh shape.
    """
    y_re, y_im = fused_mac(seed, jnp.real(t), jnp.imag(t), amp, w, K=K,
                           sigma_h2=sigma_h2, sigma_z2=sigma_z2,
                           rx_base=rx_base, u_base=u_base, n_base=n_base,
                           block_n=block_n, block_k=block_k,
                           block_u=block_u, interpret=interpret_mode())
    return jax.lax.complex(y_re, y_im)
