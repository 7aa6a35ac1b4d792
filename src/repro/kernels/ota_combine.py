"""Pallas TPU kernel for the OTA matched-filter combine (paper eq. 9/11).

The OTA receive hot-spot is a K-antenna fold of complex multiply-accumulates
over U transmitters for every symbol n:

    y[n] = sum_k conj( sum_u w_u h[u,k,n] ) * ( sum_u h[u,k,n] t[u,n] + z[k,n] )

TPU adaptation (vs. a per-symbol DSP loop on a GPU/SDR):
- complex64 is split into planar (re, im) float32 arrays so every operand
  maps onto the VPU's native f32 8x128 vector registers;
- the symbol axis N is the lane (last) dimension, blocked at `block_n`
  (multiple of 128); antennas are blocked at `block_k` and folded by
  revisiting the output block across the minor grid dimension
  (accumulate-in-VMEM reduction pattern);
- the transmitter fold (U) runs unrolled inside the block — U is small
  (M or C*M, ≤ 64) and the h slab for one (k, n) block is [U, bk, bn],
  which fits comfortably in VMEM for bk=8, bn=512.

Grid: (N // block_n, K // block_k), K minor so output revisits are
consecutive; the output block is zero-initialised at k-index 0.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _combine_kernel(h_re_ref, h_im_ref, t_re_ref, t_im_ref, z_re_ref,
                    z_im_ref, w_ref, y_ref):
    """One (n, k) block: fold block_k antennas into the y accumulator.

    Block shapes: h [U, bk, bn]; t [U, bn]; z [bk, bn]; w [U, 1];
    y [2, bn] (planar re/im rows).
    """
    ki = pl.program_id(1)

    @pl.when(ki == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    h_re = h_re_ref[...]          # [U, bk, bn]
    h_im = h_im_ref[...]
    t_re = t_re_ref[...]          # [U, bn]
    t_im = t_im_ref[...]
    w = w_ref[...]                # [U, 1]

    # received per antenna: r = sum_u h_u * t_u + z   (complex)
    r_re = z_re_ref[...]          # [bk, bn]
    r_im = z_im_ref[...]
    # matched filter: mf = sum_u w_u h_u
    mf_re = jnp.zeros_like(r_re)
    mf_im = jnp.zeros_like(r_im)
    U = h_re.shape[0]
    for u in range(U):            # unrolled: U is small (<= 64)
        hr, hi = h_re[u], h_im[u]                    # [bk, bn]
        tr, ti = t_re[u][None, :], t_im[u][None, :]  # [1, bn]
        r_re = r_re + hr * tr - hi * ti
        r_im = r_im + hr * ti + hi * tr
        wu = w[u, 0]
        mf_re = mf_re + wu * hr
        mf_im = mf_im + wu * hi

    # y += sum_k conj(mf) * r
    y_re = jnp.sum(mf_re * r_re + mf_im * r_im, axis=0)  # [bn]
    y_im = jnp.sum(mf_re * r_im - mf_im * r_re, axis=0)
    y_ref[0, :] += y_re
    y_ref[1, :] += y_im


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def ota_combine(h_re, h_im, t_re, t_im, z_re, z_im, w, *, block_n: int = 512,
                block_k: int = 8, interpret: bool = False):
    """Matched-filter combine.  h: [U,K,N]; t: [U,N]; z: [K,N]; w: [U].

    Returns (y_re [N], y_im [N]) — the un-rescaled eq. (9)/(16) output
    (caller divides by K and applies the eq. (12)/(17) rescale).
    N and K are padded to block multiples internally.
    """
    U, K, N = h_re.shape
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, K)
    Np, Kp = _round_up(N, bn), _round_up(K, bk)

    def padn(x):
        return jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Np - N)])

    if Kp != K:
        h_re = jnp.pad(h_re, ((0, 0), (0, Kp - K), (0, 0)))
        h_im = jnp.pad(h_im, ((0, 0), (0, Kp - K), (0, 0)))
        z_re = jnp.pad(z_re, ((0, Kp - K), (0, 0)))
        z_im = jnp.pad(z_im, ((0, Kp - K), (0, 0)))
    if Np != N:
        h_re, h_im = padn(h_re), padn(h_im)
        t_re, t_im = padn(t_re), padn(t_im)
        z_re, z_im = padn(z_re), padn(z_im)

    grid = (Np // bn, Kp // bk)
    h_spec = pl.BlockSpec((U, bk, bn), lambda n, k: (0, k, n))
    t_spec = pl.BlockSpec((U, bn), lambda n, k: (0, n))
    z_spec = pl.BlockSpec((bk, bn), lambda n, k: (k, n))
    w_spec = pl.BlockSpec((U, 1), lambda n, k: (0, 0))
    y_spec = pl.BlockSpec((2, bn), lambda n, k: (0, n))

    y = pl.pallas_call(
        _combine_kernel,
        grid=grid,
        in_specs=[h_spec, h_spec, t_spec, t_spec, z_spec, z_spec, w_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((2, Np), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
    )(h_re, h_im, t_re, t_im, z_re, z_im, w[:, None].astype(jnp.float32))
    return y[0, :N], y[1, :N]


def _combine_kernel_batched(h_re_ref, h_im_ref, t_re_ref, t_im_ref, z_re_ref,
                            z_im_ref, w_ref, y_ref):
    """Batched-rx variant of `_combine_kernel`: one (b, n, k) block.

    Block shapes: h [1, U, bk, bn]; t [U, bn] (shared across rx);
    z [1, bk, bn]; w [1, U]; y [1, 2, bn].  Each rx station b carries
    its own channel slab, noise and matched-filter weights.
    """
    ki = pl.program_id(2)

    @pl.when(ki == 0)
    def _init():
        y_ref[...] = jnp.zeros_like(y_ref)

    h_re = h_re_ref[0]            # [U, bk, bn]
    h_im = h_im_ref[0]
    t_re = t_re_ref[...]          # [U, bn]
    t_im = t_im_ref[...]
    w = w_ref[0, :]               # [U]

    r_re = z_re_ref[0]            # [bk, bn]
    r_im = z_im_ref[0]
    mf_re = jnp.zeros_like(r_re)
    mf_im = jnp.zeros_like(r_im)
    U = h_re.shape[0]
    for u in range(U):            # unrolled: U is small (<= 64)
        hr, hi = h_re[u], h_im[u]                    # [bk, bn]
        tr, ti = t_re[u][None, :], t_im[u][None, :]  # [1, bn]
        r_re = r_re + hr * tr - hi * ti
        r_im = r_im + hr * ti + hi * tr
        wu = w[u]
        mf_re = mf_re + wu * hr
        mf_im = mf_im + wu * hi

    y_ref[0, 0, :] += jnp.sum(mf_re * r_re + mf_im * r_im, axis=0)
    y_ref[0, 1, :] += jnp.sum(mf_re * r_im - mf_im * r_re, axis=0)


@functools.partial(jax.jit, static_argnames=("block_n", "block_k", "interpret"))
def ota_combine_batched(h_re, h_im, t_re, t_im, z_re, z_im, w, *,
                        block_n: int = 512, block_k: int = 8,
                        interpret: bool = False):
    """Matched-filter combine for B receiving stations in one dispatch.

    h: [B,U,K,N]; t: [U,N] (shared transmit symbols); z: [B,K,N];
    w: [B,U] per-rx matched-filter weights.  Returns (y_re, y_im),
    each [B, N].  Replaces B separate `ota_combine` dispatches (the old
    per-cluster Python loop) with one grid batched over the rx axis.
    """
    B, U, K, N = h_re.shape
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, K)
    Np, Kp = _round_up(N, bn), _round_up(K, bk)

    if Kp != K:
        pad_k = ((0, 0), (0, 0), (0, Kp - K), (0, 0))
        h_re, h_im = jnp.pad(h_re, pad_k), jnp.pad(h_im, pad_k)
        z_re = jnp.pad(z_re, ((0, 0), (0, Kp - K), (0, 0)))
        z_im = jnp.pad(z_im, ((0, 0), (0, Kp - K), (0, 0)))
    if Np != N:
        padn = lambda x: jnp.pad(x, [(0, 0)] * (x.ndim - 1) + [(0, Np - N)])
        h_re, h_im = padn(h_re), padn(h_im)
        t_re, t_im = padn(t_re), padn(t_im)
        z_re, z_im = padn(z_re), padn(z_im)

    grid = (B, Np // bn, Kp // bk)
    h_spec = pl.BlockSpec((1, U, bk, bn), lambda b, n, k: (b, 0, k, n))
    t_spec = pl.BlockSpec((U, bn), lambda b, n, k: (0, n))
    z_spec = pl.BlockSpec((1, bk, bn), lambda b, n, k: (b, k, n))
    w_spec = pl.BlockSpec((1, U), lambda b, n, k: (b, 0))
    y_spec = pl.BlockSpec((1, 2, bn), lambda b, n, k: (b, 0, n))

    y = pl.pallas_call(
        _combine_kernel_batched,
        grid=grid,
        in_specs=[h_spec, h_spec, t_spec, t_spec, z_spec, z_spec, w_spec],
        out_specs=y_spec,
        out_shape=jax.ShapeDtypeStruct((B, 2, Np), jnp.float32),
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(h_re, h_im, t_re, t_im, z_re, z_im, w.astype(jnp.float32))
    return y[:, 0, :N], y[:, 1, :N]


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m
