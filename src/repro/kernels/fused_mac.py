"""Fused OTA matched-filter combine with in-kernel channel generation.

`ota_combine` (the "slab" kernel) consumes a precomputed `[U, K, N]`
channel tensor from HBM, so its memory footprint — and the HBM traffic
of one hop — scales as O(U*K*N).  At the ROADMAP's target user counts
that slab cannot exist.  This kernel removes it: the Rayleigh fading
channels `h[u, k, n]` and the receiver noise `z[k, n]` are *derived on
the fly inside the kernel* from a counter-based PRNG, so the hop reads
only the `[U, N]` transmit symbols and O(block) scratch — channel
memory drops from O(U*K*N) to O(block_k * block_n).

PRNG: threefry2x32 (the same 20-round Feistel jax.random uses),
implemented with pure `jnp` uint32 ops so the kernel draws the same
values under ``interpret=True`` on CPU and compiled on TPU, and the
pure-jnp reference reproduces them (the `pltpu.prng_*` draws would not
be).  Each complex element draws
one threefry block keyed on ``(seed, rx, stream)`` with the counter
``(u * Kstride + k, n)``; the two 32-bit outputs feed a Box–Muller
transform producing the (re, im) pair.  Counters depend only on the
logical indices — never on block sizes — so every channel draw is
invariant to the blocking (outputs differ across block sizes only by
float accumulation order; pinned by tests) and exactly reproducible
outside the kernel by `fused_channels` / `fused_mac_ref`.

Counter bases: ``rx_base`` / ``u_base`` / ``n_base`` shift the *global*
logical indices the counters are built from, as explicit (traceable)
arguments rather than anything derived from block or device placement.
A caller that owns only a tile of the full (rx, u, n) index space —
e.g. one shard of the `repro.exec` device mesh — passes the tile's
origin and draws exactly the channels a full-range call would have
drawn for those indices, which is what makes the sharded combine
bitwise invariant to mesh shape.  `assert_draw_invariance` verifies
the property (offset generation == slice of the enclosing full-range
generation, bit-exact).

Padded (uneven-mesh) callers: transmitters with amp = w = 0
contribute exactly zero to both the received signal and the matched
filter, and extra rx rows with zero amplitude rows output exactly
zero — but every row still CONSUMES counter draws at its logical
indices.  The uneven-mesh executor therefore drops inactive users
*before* the call (keeping U, and with it the u-blocking and counter
range, identical to the unpadded call) and appends inactive rx rows
*after* the real ones, so real (rx, u, n) indices — and every h/z
draw — are untouched by padding (see `repro.exec.round`).

Layout mirrors `ota_combine`: planar float32 (re, im), symbol axis N in
lanes, grid ``(B_rx, N/bn, K/bk, U/bu)`` with the two reduction axes
(antennas, transmitters) minor.  The blocks meet Mosaic's (8, 128)
tiling for every bu: t is laid out ``[U/bu, bu, N]`` (a block spans
the whole bu axis), the per-user amp / w scalars and the counter words
live in SMEM, and inside a block the users are visited one at a time
(`_block_sums`), so the live channel tile is [bk, bn], never
[bu, bk, bn].  Received signal and matched filter are accumulated in
VMEM scratch over the U axis; the output block is revisited across K
and finalized at the last U step.  The B_rx axis
batches receiving stations (cluster hop: one dispatch for all C ISs,
each with its own `[U]` amplitude row and matched-filter mask) — every
rx draws independent channels, as in the paper's model.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_GOLDEN = np.uint32(0x9E3779B9)   # odd -> multiplication is bijective mod 2^32
_STREAM = np.uint32(0x85EBCA77)
_TAG_CHAN = np.uint32(1)
_TAG_NOISE = np.uint32(2)
_U24 = np.float32(2.0 ** -24)
# Box-Muller's angle 2*pi*m / 2^24: the step in radians, and the
# minimax coefficients of sin and cos on [-pi/4, pi/4] (Cephes sinf /
# cosf)
_TURN24 = np.float32(2.0 * np.pi / 2.0 ** 24)
_S1, _S2, _S3 = (np.float32(-1.6666654611e-1), np.float32(8.3321608736e-3),
                 np.float32(-1.9515295891e-4))
_C1, _C2, _C3 = (np.float32(4.166664568298827e-2),
                 np.float32(-1.388731625493765e-3),
                 np.float32(2.443315711809948e-5))


def _round_up(x: int, m: int) -> int:
    return (x + m - 1) // m * m


def canonical_block_u(M: int, cap: int = 1024) -> int:
    """The u-block size every fused *cluster-hop* path shares.

    The partial-combine mode (`fused_mac_partials`) makes the per-user
    accumulation order observable across devices, so bitwise equality
    between the single engine, the gathered sharded hop and the
    u-sharded partial fold requires all three to tile the user axis
    identically.  This canonical size is a pure function of the
    per-cluster user count M: it always divides M (so u-blocks never
    straddle a cluster — and with it a u-shard — boundary) and halves
    down from M only while above `cap`.  The cap bounds the kernel's
    double-buffered [bu, bn] symbol blocks (8 MiB at bu = 1024,
    bn = 512: half of a v5e's scoped VMEM) and the interpret-mode grid
    overhead.  Every bu is legal on a TPU, M = 5 included: the symbol
    blocks span the whole bu axis and amp / w are SMEM scalars.
    """
    bu = max(int(M), 1)
    while bu > cap and bu % 2 == 0:
        bu //= 2
    return bu


def _k_stride(K: int) -> int:
    """Counter stride of the antenna axis: fixed per K (never per block
    size) so draws are invariant to blocking.  Uniqueness of the
    ``u * Kstride + k`` counter word requires U * Kstride < 2^32."""
    return _round_up(max(K, 1), 128)


# ---------------------------------------------------------------------------
# counter-based PRNG: threefry2x32 + Box-Muller, pure jnp uint32 ops
# ---------------------------------------------------------------------------

_ROTATIONS = (13, 15, 26, 6, 17, 29, 16, 24)   # round n rotates by [n % 8]


def _rotl(x, r: int):
    return (x << r) | (x >> (32 - r))


def _threefry_head(k0, k1, c0, c1, d0=0, d1=0):
    """The part of threefry2x32's round 1 that is affine in its inputs,
    on the counter words (c0 + d0, c1 + d1) with d0, d1 scalars:
    ``(x0, y)`` with x0 = c0 + (d0 + d1 + k0 + k1) + c1, the first word
    after the round's add, and y = c1 + (d1 + k1), the second before its
    rotation.  The scalar offsets fold into the key words.

    Every add is mod 2^32, so shifting the counters or the keys by
    scalars shifts x0 and y by scalars: the kernel's users of one tile,
    whose counters differ by j * Kstride in c0, share one head and start
    their `_threefry_tail` at ``x0 + j * Kstride``."""
    return c0 + (d0 + d1 + k0 + k1) + c1, c1 + (d1 + k1)


def _rekey_head(head, keys, new_keys, d0=0):
    """The `_threefry_head` of the counters (c0 - d0, c1) under
    `new_keys`, from `head`, that of (c0, c1) under `keys`: both words
    shift by scalars."""
    (x0, y), (k0, k1), (j0, j1) = head, keys, new_keys
    return x0 + (j0 + j1 - k0 - k1 - d0), y + (j1 - k1)


def _threefry_tail(k0, k1, x0, y):
    """The rest of threefry2x32 from `_threefry_head`: round 1's
    rotation and xor, rounds 2 to 20 and the five key injections.  Each
    injection adds one key-schedule word to each half: injection s's
    constant s is folded into its key word first, a scalar add wherever
    the keys are scalars."""
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))

    def inject(s, x0, x1):                     # injection s = 1..5
        return x0 + ks[s % 3], x1 + (ks[(s + 1) % 3] + np.uint32(s))

    x1 = _rotl(y, _ROTATIONS[0]) ^ x0
    for n in range(1, 20):
        if n % 4 == 0:
            x0, x1 = inject(n // 4, x0, x1)
        x0 = x0 + x1
        x1 = _rotl(x1, _ROTATIONS[n % 8]) ^ x0
    return inject(5, x0, x1)


def _threefry2x32(k0, k1, x0, x1):
    """The 20-round threefry2x32 block cipher (matches jax.random's
    generator algorithm; arbitrary uint32 array shapes)."""
    return _threefry_tail(k0, k1, *_threefry_head(k0, k1, x0, x1))


def _sincos_turn24(m):
    """(sin, cos) of the angle 2*pi*m / 2^24, m int32 in [0, 2^24).

    The quadrant reduction is exact in integers: j is the nearest
    quarter turn and the remainder m - j * 2^22 lies in [-2^21, 2^21),
    so x = remainder * 2*pi / 2^24 in [-pi/4, pi/4) carries one f32
    rounding.  Two short minimax polynomials (Cephes sinf / cosf) give
    sin x and cos x; the quadrant q = j mod 4 swaps and negates them.
    A generic f32 `sin` / `cos` would instead redo a Payne-Hanek style
    range reduction of an already-rounded angle: more VPU work and a
    larger error (max 1.1e-7 here against float64, 4.1e-7 that way).
    """
    j = (m + (1 << 21)) >> 22             # quarter turns of 2^22: 0..4
    x = (m - (j << 22)).astype(jnp.float32) * _TURN24
    z = x * x
    s = x + x * z * (_S1 + z * (_S2 + z * _S3))
    c = 1.0 - 0.5 * z + z * z * (_C1 + z * (_C2 + z * _C3))
    q = j & 3
    odd = (q & 1) == 1
    sin_, cos_ = jnp.where(odd, c, s), jnp.where(odd, s, c)
    sin_ = jnp.where(q >= 2, -sin_, sin_)
    cos_ = jnp.where((q == 1) | (q == 2), -cos_, cos_)
    return sin_, cos_


def _box_muller(b0, b1):
    """Two uint32 words -> two independent N(0, 1) float32 draws."""
    # u1 in (0, 1] (log-safe), angle 2*pi*m2 / 2^24; 24-bit precision
    # the 24-bit values fit int32 exactly (Mosaic has no uint32 -> f32)
    u1 = 1.0 - (b0 >> 8).astype(jnp.int32).astype(jnp.float32) * _U24
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    sin_, cos_ = _sincos_turn24((b1 >> 8).astype(jnp.int32))
    return r * cos_, r * sin_


def _cx_normal(key0, key1, x0, y, sigma: float):
    """Per-element CN(0, 2*sigma^2) draw, (re, im) each N(0, sigma^2),
    from the block's `_threefry_head` words (x0, y)."""
    n0, n1 = _box_muller(*_threefry_tail(key0, key1, x0, y))
    return sigma * n0, sigma * n1


def _stream_keys(s0, s1, rx, tag):
    """Fold (rx index, stream tag) into the seed words.  Distinct
    (rx, tag) pairs give distinct threefry keys, hence independent
    streams (threefry is a PRF over (key, counter))."""
    rx = jnp.asarray(rx, jnp.uint32)
    tagc = np.uint32((int(tag) * int(_STREAM)) & 0xFFFFFFFF)
    return s0 + rx * _GOLDEN, s1 + tagc


# ---------------------------------------------------------------------------
# the fused kernel
# ---------------------------------------------------------------------------

_GROUP = 8   # users per sublane-aligned group of the in-block user loop


def _counter_words(seed, rx_base, u_base, n_base):
    """uint32 [5]: the two seed words, then the global counter bases
    (rx, u, n) — the kernel's scalar operands, kept in SMEM."""
    base = jnp.stack([jnp.asarray(0 if v is None else v, jnp.uint32)
                      for v in (rx_base, u_base, n_base)])
    return jnp.concatenate([jnp.asarray(seed).astype(jnp.uint32).reshape(2),
                            base])


def _tile_counters(words_ref, bk: int, bn: int):
    """(rx, sk, ln, k0, n0): this grid step's rx counter word and its
    (k, n) tile's antenna and symbol counter words kk = sk + k0 and
    nn = ln + n0, each as a [bk, bn] iota (sublane sk, lane ln) and a
    scalar base, kept apart so that the bases fold into the key words
    (`_threefry_head`)."""
    rx = words_ref[2] + pl.program_id(0).astype(jnp.uint32)
    k0 = (pl.program_id(2) * bk).astype(jnp.uint32)
    n0 = (pl.program_id(1) * bn).astype(jnp.uint32) + words_ref[4]
    sk = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 0)
    ln = jax.lax.broadcasted_iota(jnp.uint32, (bk, bn), 1)
    return rx, sk, ln, k0, n0


def _channel_head(words_ref, rx, sk, ln, k0, n0, *, Kstride: int, bu: int):
    """(keys, head, d): the channel stream's keys (hk0, hk1) and the
    `_threefry_head` of this u-block's first user u0 on its (k, n)
    tile, whose counters are (u0 * Kstride + kk, nn); d = u0 * Kstride."""
    hk0, hk1 = _stream_keys(words_ref[0], words_ref[1], rx, _TAG_CHAN)
    u0 = (pl.program_id(3) * bu).astype(jnp.uint32) + words_ref[3]
    d = u0 * np.uint32(Kstride)
    return (hk0, hk1), _threefry_head(hk0, hk1, sk, ln, k0 + d, n0), d


def _block_sums(t_re_ref, t_im_ref, amp_ref, w_ref, keys, head, *,
                Kstride: int, sigma_h: float, bu: int):
    """This u-block's accumulators, each [bk, bn]:

        r  = sum_u h[u] t[u]      mf = sum_u w[u] h[u]

    summed from zero over the block's users in ascending order — the
    per-block term both kernels share.  Users are visited in
    sublane-aligned groups of `_GROUP` (a `fori_loop`, plus a static
    tail when bu is not a multiple of the group), one user at a time:
    each user's channel tile is drawn, folded and dropped, so no
    [bu, bk, bn] temporary ever exists.  amp/w are per-user scalars
    read from SMEM; each t row broadcasts over the antenna sublanes.

    `keys` and `head` are `_channel_head`'s: user u0 + j's counter is
    user u0's plus j * Kstride in the first word, so its draw starts
    from the shared head's x0 plus that scalar (a zero for the static
    j = 0, which Mosaic folds away).
    """
    x0, y = head

    def user(j, t_re, t_im, acc):
        a = amp_ref[0, j]
        wa = w_ref[0, j] * a                 # matched filter uses w_u * h_u
        dj = jax.lax.convert_element_type(j, jnp.uint32) * np.uint32(Kstride)
        g_re, g_im = _cx_normal(*keys, x0 + dj, y, sigma_h)
        h_re, h_im = a * g_re, a * g_im
        r_re, r_im, mf_re, mf_im = acc
        return (r_re + (h_re * t_re - h_im * t_im),
                r_im + (h_re * t_im + h_im * t_re),
                mf_re + wa * g_re, mf_im + wa * g_im)

    def group(start, size, acc):
        t_re = t_re_ref[pl.ds(start, size), :]            # [size, bn]
        t_im = t_im_ref[pl.ds(start, size), :]
        for i in range(size):
            acc = user(start + i, t_re[i:i + 1], t_im[i:i + 1], acc)
        return acc

    zero = jnp.zeros(x0.shape, jnp.float32)
    acc = (zero, zero, zero, zero)
    n_full = bu // _GROUP
    if n_full:
        acc = jax.lax.fori_loop(
            0, n_full,
            lambda g, acc: group(pl.multiple_of(g * _GROUP, _GROUP),
                                 _GROUP, acc), acc)
    if bu % _GROUP:
        acc = group(n_full * _GROUP, bu % _GROUP, acc)
    return acc


def _fused_kernel(words_ref, t_re_ref, t_im_ref, amp_ref, w_ref, y_ref,
                  r_re, r_im, mf_re, mf_im, *, K: int, Kstride: int,
                  sigma_h: float, sigma_z: float, bu: int, bk: int, bn: int):
    """One (rx, n, k, u) grid step.

    `words_ref` (SMEM, uint32 [5]) holds the seed words and the global
    counter bases (see `_counter_words`).  Scratch r (received signal)
    and mf (matched filter), both [bk, bn], start from the noise z and
    zero at the first u step and gain one `_block_sums` term per u
    step; y [2, bn] accumulates the conj(mf) * r antenna fold over the
    K grid axis.
    """
    ki, ui = pl.program_id(2), pl.program_id(3)
    rx, sk, ln, k0, n0 = _tile_counters(words_ref, bk, bn)
    keys, head, d = _channel_head(
        words_ref, rx, sk, ln, k0, n0, Kstride=Kstride, bu=bu)

    @pl.when(ui == 0)
    def _init_block():
        # receiver noise z ~ CN(0, sigma_z2) seeds the r accumulator;
        # its counters (kk, nn) are user u0's less d in the first word
        zk = _stream_keys(words_ref[0], words_ref[1], rx, _TAG_NOISE)
        z_re, z_im = _cx_normal(*zk, *_rekey_head(head, keys, zk, d),
                                sigma_z)
        r_re[...] = z_re
        r_im[...] = z_im
        mf_re[...] = jnp.zeros_like(mf_re)
        mf_im[...] = jnp.zeros_like(mf_im)

    pr_re, pr_im, pm_re, pm_im = _block_sums(
        t_re_ref, t_im_ref, amp_ref, w_ref, keys, head, Kstride=Kstride,
        sigma_h=sigma_h, bu=bu)
    r_re[...] += pr_re
    r_im[...] += pr_im
    mf_re[...] += pm_re
    mf_im[...] += pm_im

    @pl.when(ui == pl.num_programs(3) - 1)
    def _finish_block():
        @pl.when(ki == 0)
        def _init_out():
            y_ref[...] = jnp.zeros_like(y_ref)

        # padded antenna rows (kk = sk + k0 >= K) carry generated
        # garbage: mask them out
        mask = (sk < np.uint32(K) - k0).astype(jnp.float32)
        a, b = mf_re[...], mf_im[...]
        p, q = r_re[...], r_im[...]
        y_ref[0:1, :] += jnp.sum(mask * (a * p + b * q), axis=0,
                                 keepdims=True)
        y_ref[1:2, :] += jnp.sum(mask * (a * q - b * p), axis=0,
                                 keepdims=True)


def _blocking(N: int, K: int, block_n: int, block_k: int):
    bn = min(block_n, _round_up(N, 128))
    bk = min(block_k, K)
    if bk > 128:
        raise ValueError(f"block_k must be <= 128, got {bk}")
    return bn, bk


def _kernel_operands(t_re, t_im, amp, w, bu: int, Np: int):
    """Lay the operands out for the grid: t [U, Np] -> [G, bu, Np], so a
    (bu, bn) block spans the whole bu axis and meets the (8, 128) tiling
    for every bu, and amp / w [B, U] -> [B, G, 1, bu] SMEM blocks."""
    B, U = amp.shape
    G = U // bu
    t_re = t_re.reshape(G, bu, Np)
    t_im = t_im.reshape(G, bu, Np)
    amp = amp.astype(jnp.float32).reshape(B, G, 1, bu)
    w = w.astype(jnp.float32).reshape(B, G, 1, bu)
    return t_re, t_im, amp, w


def _in_specs(bu: int, bn: int):
    words = pl.BlockSpec(memory_space=pltpu.SMEM)
    t = pl.BlockSpec((None, bu, bn), lambda b, n, k, u: (u, 0, n))
    a = pl.BlockSpec((None, None, 1, bu), lambda b, n, k, u: (b, u, 0, 0),
                     memory_space=pltpu.SMEM)
    return [words, t, t, a, a]


@functools.partial(
    jax.jit, static_argnames=("K", "sigma_h2", "sigma_z2", "block_n",
                              "block_k", "block_u", "interpret"))
def fused_mac(seed, t_re, t_im, amp, w, *, K: int, sigma_h2: float,
              sigma_z2: float, rx_base=None, u_base=None, n_base=None,
              block_n: int = 512, block_k: int = 8,
              block_u: int = 32, interpret: bool = False):
    """Fused OTA combine over K on-the-fly Rayleigh antennas:

        y[b, n] = sum_k conj(sum_u w[b,u] h[b,u,k,n])
                        * (sum_u h[b,u,k,n] t[u,n] + z[b,k,n])

    with h[b,u,k,n] = amp[b,u] * g, g ~ CN(0, sigma_h2) and
    z ~ CN(0, sigma_z2) derived in-kernel from `seed` (uint32 [2]).
    No [U, K, N] array is ever materialized.

    t: float32 [U, N] planar pair (transmit symbols, caller pre-scales
    by P); amp, w: float32 [B, U].  Returns (y_re, y_im), each [B, N]
    — un-rescaled, as `ota_combine` (caller divides by K and applies
    the eq. (12)/(17) rescale).  Channel draws are invariant to block
    sizes (outputs differ only by float accumulation order).

    `rx_base` / `u_base` / `n_base` (int or traced uint32 scalar,
    default 0) shift the global logical indices behind the counter
    PRNG: a call over a (rx, u, n) tile of a larger index space draws
    exactly the channels the full-range call draws there, so sharded
    callers (repro.exec) stay bitwise-invariant to the mesh shape.
    """
    U, N = t_re.shape
    B = amp.shape[0]
    bn, bk = _blocking(N, K, block_n, block_k)
    bu = min(block_u, U)
    Np, Kp, Up = _round_up(N, bn), _round_up(K, bk), _round_up(U, bu)

    # zero-pad: padded transmitters have amp = w = 0 and add exact
    # zeros (they draw at their own counter indices, past the real
    # users'); padded antennas are masked in-kernel; padded symbols are
    # sliced off below.
    if Np != N:
        t_re = jnp.pad(t_re, ((0, 0), (0, Np - N)))
        t_im = jnp.pad(t_im, ((0, 0), (0, Np - N)))
    if Up != U:
        t_re = jnp.pad(t_re, ((0, Up - U), (0, 0)))
        t_im = jnp.pad(t_im, ((0, Up - U), (0, 0)))
        amp = jnp.pad(amp, ((0, 0), (0, Up - U)))
        w = jnp.pad(w, ((0, 0), (0, Up - U)))

    G = Up // bu
    grid = (B, Np // bn, Kp // bk, G)
    kernel = functools.partial(
        _fused_kernel, K=K, Kstride=_k_stride(K),
        sigma_h=float(np.sqrt(sigma_h2 / 2.0)),
        sigma_z=float(np.sqrt(sigma_z2 / 2.0)), bu=bu, bk=bk, bn=bn)

    y = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_in_specs(bu, bn),
        out_specs=pl.BlockSpec((None, 2, bn), lambda b, n, k, u: (b, 0, n)),
        out_shape=jax.ShapeDtypeStruct((B, 2, Np), jnp.float32),
        scratch_shapes=[pltpu.VMEM((bk, bn), jnp.float32)] * 4,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "arbitrary", "arbitrary")),
    )(_counter_words(seed, rx_base, u_base, n_base),
      *_kernel_operands(t_re, t_im, amp, w, bu, Np))
    return y[:, 0, :N], y[:, 1, :N]


# ---------------------------------------------------------------------------
# partial-combine mode: per-u-tile accumulators + pinned-order fold
# ---------------------------------------------------------------------------

def _fused_partial_kernel(words_ref, t_re_ref, t_im_ref, amp_ref, w_ref,
                          pr_re_ref, pr_im_ref, pm_re_ref, pm_im_ref, *,
                          Kstride: int, sigma_h: float, bu: int, bk: int,
                          bn: int):
    """One (rx, n, k, u) grid step of `fused_mac_partials`: the same
    `_block_sums` term `_fused_kernel` folds into its scratch, written
    to this block's own output slot instead, so a caller owning only a
    tile of the user axis can emit its blocks and a pinned-order fold
    of the blocks replays the full kernel's accumulation
    (`fused_partials_reduce`).  No noise: z is a separate term keyed on
    the same counter stream (`fused_noise`).
    """
    keys, head, _ = _channel_head(
        words_ref, *_tile_counters(words_ref, bk, bn), Kstride=Kstride,
        bu=bu)
    pr_re, pr_im, pm_re, pm_im = _block_sums(
        t_re_ref, t_im_ref, amp_ref, w_ref, keys, head, Kstride=Kstride,
        sigma_h=sigma_h, bu=bu)
    pr_re_ref[...] = pr_re
    pr_im_ref[...] = pr_im
    pm_re_ref[...] = pm_re
    pm_im_ref[...] = pm_im


@functools.partial(
    jax.jit, static_argnames=("K", "sigma_h2", "block_n", "block_k",
                              "block_u", "interpret"))
def fused_mac_partials(seed, t_re, t_im, amp, w, *, K: int, sigma_h2: float,
                       rx_base=None, u_base=None, n_base=None,
                       block_n: int = 512, block_k: int = 8,
                       block_u: int = 32, interpret: bool = False):
    """Partial-combine mode of `fused_mac`: per-u-block accumulators.

    Same contract as `fused_mac` for t [U, N] / amp, w [B, U] and the
    counter bases, except that U must be a multiple of `block_u` (the
    caller aligns its tile to the canonical blocking —
    `canonical_block_u`) and the result is the K-resolved
    *pre-contraction* accumulator blocks

        pr[b, g, k, n] = sum_{u in block g} h[b,u,k,n] t[u,n]   (re, im)
        pm[b, g, k, n] = sum_{u in block g} w[b,u] h[b,u,k,n]   (re, im)

    as four float32 [B, G, Kp, N] arrays with G = U // block_u and Kp
    the padded antenna row count (``_round_up(K, block_k)`` — padded
    rows carry the same generated garbage the full kernel masks at its
    finalize, and `fused_partials_reduce` masks identically).  Noise is
    NOT included: draw it once globally with `fused_noise` and hand it
    to the fold.  Summing a tile's blocks into the enclosing call's
    fold in ascending global block order replays `fused_mac`'s scratch
    accumulation.
    """
    U, N = t_re.shape
    B = amp.shape[0]
    bn, bk = _blocking(N, K, block_n, block_k)
    bu = block_u
    if U % bu:
        raise ValueError(
            f"partial combine needs U ({U}) divisible by block_u ({bu}) "
            f"so u-blocks align across tiles")
    Np, Kp = _round_up(N, bn), _round_up(K, bk)
    G = U // bu

    if Np != N:
        t_re = jnp.pad(t_re, ((0, 0), (0, Np - N)))
        t_im = jnp.pad(t_im, ((0, 0), (0, Np - N)))

    grid = (B, Np // bn, Kp // bk, G)
    kernel = functools.partial(
        _fused_partial_kernel, Kstride=_k_stride(K),
        sigma_h=float(np.sqrt(sigma_h2 / 2.0)), bu=bu, bk=bk, bn=bn)
    p_spec = pl.BlockSpec((None, None, bk, bn),
                          lambda b, n, k, u: (b, u, k, n))
    p_shape = jax.ShapeDtypeStruct((B, G, Kp, Np), jnp.float32)

    # every grid step writes its own disjoint output block — no scratch
    # carry, so all four axes are parallel when compiled
    pr_re, pr_im, pm_re, pm_im = pl.pallas_call(
        kernel,
        grid=grid,
        in_specs=_in_specs(bu, bn),
        out_specs=[p_spec] * 4,
        out_shape=[p_shape] * 4,
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(dimension_semantics=(
            "parallel", "parallel", "parallel", "parallel")),
    )(_counter_words(seed, rx_base, u_base, n_base),
      *_kernel_operands(t_re, t_im, amp, w, bu, Np))
    return (pr_re[..., :N], pr_im[..., :N],
            pm_re[..., :N], pm_im[..., :N])


def fused_noise(seed, B: int, K: int, N: int, sigma_z2: float,
                rx_base=0, n_base=0):
    """The kernel's receiver-noise draws, as a separate term.

    Returns (z_re, z_im), each float32 [B, K, N] — bitwise the z values
    `_fused_kernel` seeds its r scratch with at ``ui == 0`` (same
    `_TAG_NOISE` stream, same ``(k, n + n_base)`` counters; threefry +
    Box-Muller are elementwise, so blocking cannot change a draw).
    Partial-combine callers pass the *padded* antenna row count Kp for
    K: the full kernel draws z for its padded rows too and masks them
    only at the finalize, so the fold must replay exactly that.
    """
    seed = jnp.asarray(seed).astype(jnp.uint32).reshape(2)
    kk = jnp.arange(K, dtype=jnp.uint32)[:, None]
    nn = (jnp.arange(N, dtype=jnp.uint32)
          + jnp.asarray(n_base, jnp.uint32))[None, :]
    w0 = jnp.broadcast_to(kk, (K, N))
    w1 = jnp.broadcast_to(nn, (K, N))
    s_z = float(np.sqrt(sigma_z2 / 2.0))

    def one_rx(b):
        zk0, zk1 = _stream_keys(seed[0], seed[1], b, _TAG_NOISE)
        return _cx_normal(zk0, zk1, *_threefry_head(zk0, zk1, w0, w1), s_z)

    rx0 = jnp.asarray(rx_base, jnp.uint32)
    return jax.lax.map(one_rx, jnp.arange(B, dtype=jnp.uint32) + rx0)


def fused_partials_reduce(pr_re, pr_im, pm_re, pm_im, z_re, z_im, *,
                          K: int, block_k: int = 8):
    """Pinned-order fold of partial-combine blocks -> `fused_mac`'s y.

    pr/pm: float32 [B, G, Kp, N] per-u-block accumulators
    (`fused_mac_partials`), already concatenated in ascending *global*
    block order and pre-sliced to exactly the blocks to fold (a caller
    with trailing inactive blocks drops them here, not with zero adds);
    z: float32 [B, Kp, N] noise (`fused_noise` over the padded Kp).

    Replays the full kernel's accumulation order exactly: r starts from
    z and mf from zero (the ``ui == 0`` scratch init), blocks fold in
    ascending order via `fori_loop` — a fixed sequential chain, never a
    `psum`, whose accumulation order would follow the device count —
    and the finalize masks padded antenna rows and contracts one
    block_k-row block at a time in ascending k order, matching the
    kernel's K grid axis.  Returns (y_re, y_im), each [B, N], bitwise
    `fused_mac` on the enclosing full user range.

    Bitwise caveat: XLA:CPU's fusion (FMA formation) of the finalize's
    ``a * p + b * q`` depends on the enclosing program, so the equality
    holds when partials and fold run inside ONE jitted program — the
    shape of both the sharded executor and `fused_mac` itself (whose
    interpret-mode kernel is inlined jax ops under its own jit).
    Calling the pieces eagerly op-by-op computes the same sums with a
    different rounding of the contraction.  tests/test_fused_mac.py
    pins the one-program equality across tilings and padded K/N.
    """
    B, G, Kp, N = pr_re.shape
    bk = min(block_k, _round_up(K, 1))
    if Kp != _round_up(K, bk):
        raise ValueError(
            f"partials carry Kp={Kp} antenna rows but K={K}, "
            f"block_k={bk} implies {_round_up(K, bk)}")

    def fold(g, acc):
        r_re, r_im, mf_re, mf_im = acc
        return (r_re + pr_re[:, g], r_im + pr_im[:, g],
                mf_re + pm_re[:, g], mf_im + pm_im[:, g])

    init = (z_re, z_im, jnp.zeros_like(z_re), jnp.zeros_like(z_im))
    r_re, r_im, mf_re, mf_im = jax.lax.fori_loop(0, G, fold, init)

    kk = np.arange(Kp, dtype=np.uint32)
    y_re = jnp.zeros((B, N), jnp.float32)
    y_im = jnp.zeros((B, N), jnp.float32)
    for ki in range(Kp // bk):
        sl = slice(ki * bk, (ki + 1) * bk)
        mask = jnp.asarray(
            (kk[sl] < np.uint32(K)).astype(np.float32))[None, :, None]
        a, b = mf_re[:, sl], mf_im[:, sl]
        p, q = r_re[:, sl], r_im[:, sl]
        y_re = y_re + jnp.sum(mask * (a * p + b * q), axis=1)
        y_im = y_im + jnp.sum(mask * (a * q - b * p), axis=1)
    return y_re, y_im


# ---------------------------------------------------------------------------
# pure-jnp reference: same draws, materialized (tests / small shapes)
# ---------------------------------------------------------------------------

def fused_channels(seed, B: int, U: int, K: int, N: int, sigma_h2: float,
                   sigma_z2: float, rx_base=0, u_base=0, n_base=0):
    """Materialize the exact channel realizations the kernel derives:
    g [B, U, K, N] complex64 ~ CN(0, sigma_h2) (unit amplitude — caller
    applies amp) and z [B, K, N] ~ CN(0, sigma_z2).  O(B*U*K*N) memory:
    for tests and small-shape oracles only.

    The counter bases shift the global (rx, u, n) indices exactly as in
    `fused_mac`: with bases (rb, ub, nb) the returned g equals the
    [rb:rb+B, ub:ub+U, :, nb:nb+N] slice of the base-0 generation
    (bit-exact; `assert_draw_invariance` checks it)."""
    seed = jnp.asarray(seed).astype(jnp.uint32).reshape(2)
    Kstride = np.uint32(_k_stride(K))
    uu = (jnp.arange(U, dtype=jnp.uint32)
          + jnp.asarray(u_base, jnp.uint32))[:, None, None]
    kk = jnp.arange(K, dtype=jnp.uint32)[None, :, None]
    nn = (jnp.arange(N, dtype=jnp.uint32)
          + jnp.asarray(n_base, jnp.uint32))[None, None, :]
    w0_h = jnp.broadcast_to(uu * Kstride + kk, (U, K, N))
    w1_h = jnp.broadcast_to(nn, (U, K, N))
    w0_z = jnp.broadcast_to(kk[0], (K, N))
    w1_z = jnp.broadcast_to(nn[0], (K, N))
    s_h = float(np.sqrt(sigma_h2 / 2.0))
    s_z = float(np.sqrt(sigma_z2 / 2.0))

    def one_rx(b):
        hk0, hk1 = _stream_keys(seed[0], seed[1], b, _TAG_CHAN)
        zk0, zk1 = _stream_keys(seed[0], seed[1], b, _TAG_NOISE)
        g = jax.lax.complex(*_cx_normal(
            hk0, hk1, *_threefry_head(hk0, hk1, w0_h, w1_h), s_h))
        z = jax.lax.complex(*_cx_normal(
            zk0, zk1, *_threefry_head(zk0, zk1, w0_z, w1_z), s_z))
        return g, z

    rx0 = jnp.asarray(rx_base, jnp.uint32)
    g, z = jax.lax.map(one_rx, jnp.arange(B, dtype=jnp.uint32) + rx0)
    return g, z


def assert_draw_invariance(seed, B: int, U: int, K: int, N: int,
                           sigma_h2: float = 1.0, sigma_z2: float = 1.0,
                           *, rx_base: int = 0, u_base: int = 0,
                           n_base: int = 0) -> None:
    """Assert (bit-exact) that offset generation equals the matching
    slice of the enclosing full-range generation — the invariant the
    sharded executor relies on when it hands each mesh shard its tile
    origin instead of the full index space."""
    g_o, z_o = fused_channels(seed, B, U, K, N, sigma_h2, sigma_z2,
                              rx_base=rx_base, u_base=u_base, n_base=n_base)
    g_f, z_f = fused_channels(seed, rx_base + B, u_base + U, K, n_base + N,
                              sigma_h2, sigma_z2)
    ok_g = bool(jnp.all(g_o == g_f[rx_base:, u_base:, :, n_base:]))
    ok_z = bool(jnp.all(z_o == z_f[rx_base:, :, n_base:]))
    if not (ok_g and ok_z):
        raise AssertionError(
            f"counter-offset draws diverge from the full-range slice "
            f"(g ok={ok_g}, z ok={ok_z}) for bases "
            f"rx={rx_base}, u={u_base}, n={n_base}")


def fused_mac_ref(seed, t_re, t_im, amp, w, *, K: int, sigma_h2: float,
                  sigma_z2: float, rx_base=0, u_base=0, n_base=0):
    """Einsum oracle for `fused_mac`: materializes the same channel
    realizations (identical counters, identical counter bases) and
    folds them the slab way.  Must agree with the kernel to
    float-accumulation error."""
    U, N = t_re.shape
    B = amp.shape[0]
    g, z = fused_channels(seed, B, U, K, N, sigma_h2, sigma_z2,
                          rx_base=rx_base, u_base=u_base, n_base=n_base)
    t = jax.lax.complex(t_re, t_im)
    h = amp.astype(jnp.complex64)[:, :, None, None] * g       # [B,U,K,N]
    # full f32 products on every backend (a TPU einsum defaults to bf16)
    hi = jax.lax.Precision.HIGHEST
    r = jnp.einsum("bukn,un->bkn", h, t, precision=hi) + z
    mf = jnp.einsum("bu,bukn->bkn", w.astype(jnp.complex64), h, precision=hi)
    y = jnp.sum(jnp.conj(mf) * r, axis=1)                     # [B, N]
    return jnp.real(y), jnp.imag(y)
