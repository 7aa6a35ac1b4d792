"""Fused on-the-fly-channel kernel vs its materialized jnp oracle.

The contract pinned here is what the CI parity gate relies on: the
in-kernel counter PRNG derives *exactly* the channels `fused_channels`
materializes, independent of blocking, and the fused fold agrees with
the einsum oracle to float-accumulation error (<= 1e-4 relative).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels import (assert_draw_invariance, canonical_block_u,
                           fused_channels, fused_mac, fused_mac_partials,
                           fused_mac_ref, fused_noise, fused_partials_reduce)
from repro.kernels.fused_mac import _box_muller, _sincos_turn24

SEED = jnp.asarray([0xC0FFEE, 42], jnp.uint32)


def _mk(rng, B, U, N):
    t_re = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
    t_im = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
    amp = jnp.asarray(rng.uniform(0.5, 2.0, (B, U)), jnp.float32)
    w = jnp.asarray(rng.integers(0, 2, (B, U)), jnp.float32)
    return t_re, t_im, amp, w


SHAPES = [
    (1, 1, 1, 64),      # degenerate
    (1, 4, 8, 256),     # aligned
    (3, 5, 7, 130),     # unaligned everything (padding paths)
    (2, 33, 16, 513),   # prime-ish
    (1, 70, 100, 1000), # paper-scale antennas, unaligned U and K
]


@pytest.mark.parametrize("B,U,K,N", SHAPES)
def test_fused_matches_materialized_oracle(B, U, K, N):
    rng = np.random.default_rng(B * 100 + U + K + N)
    t_re, t_im, amp, w = _mk(rng, B, U, N)
    kw = dict(K=K, sigma_h2=1.0, sigma_z2=2.0)
    yr, yi = fused_mac(SEED, t_re, t_im, amp, w, interpret=True, **kw)
    rr, ri = fused_mac_ref(SEED, t_re, t_im, amp, w, **kw)
    scale = float(jnp.abs(jax.lax.complex(rr, ri)).max()) + 1e-12
    assert float(jnp.abs(yr - rr).max()) / scale < 1e-4
    assert float(jnp.abs(yi - ri).max()) / scale < 1e-4


def test_draws_invariant_to_block_sizes():
    """Counters depend on logical indices only — changing the blocking
    must reproduce the same channel realizations (outputs equal up to
    float accumulation order)."""
    rng = np.random.default_rng(7)
    t_re, t_im, amp, w = _mk(rng, 2, 12, 700)
    kw = dict(K=24, sigma_h2=1.0, sigma_z2=1.0, interpret=True)
    y1 = fused_mac(SEED, t_re, t_im, amp, w, block_n=512, block_k=8,
                   block_u=32, **kw)
    y2 = fused_mac(SEED, t_re, t_im, amp, w, block_n=128, block_k=4,
                   block_u=5, **kw)
    scale = float(jnp.abs(y1[0]).max())
    np.testing.assert_allclose(np.asarray(y1[0]), np.asarray(y2[0]),
                               atol=1e-4 * scale)
    np.testing.assert_allclose(np.asarray(y1[1]), np.asarray(y2[1]),
                               atol=1e-4 * scale)


def test_seed_determinism_and_sensitivity():
    rng = np.random.default_rng(3)
    t_re, t_im, amp, w = _mk(rng, 1, 6, 256)
    kw = dict(K=8, sigma_h2=1.0, sigma_z2=1.0, interpret=True)
    a1 = fused_mac(SEED, t_re, t_im, amp, w, **kw)
    a2 = fused_mac(SEED, t_re, t_im, amp, w, **kw)
    b = fused_mac(jnp.asarray([1, 2], jnp.uint32), t_re, t_im, amp, w, **kw)
    np.testing.assert_array_equal(np.asarray(a1[0]), np.asarray(a2[0]))
    np.testing.assert_array_equal(np.asarray(a1[1]), np.asarray(a2[1]))
    assert float(jnp.abs(a1[0] - b[0]).max()) > 0.0


def test_counter_bases_reproduce_full_range_slices():
    """The sharding contract: generation at counter bases (rb, ub, nb)
    is bit-exactly the [rb:, ub:, :, nb:] slice of the base-0
    generation — a shard handed its tile origin draws the channels of
    its global indices, independent of the mesh."""
    B, U, K, N = 2, 3, 5, 48
    rb, ub, nb = 1, 2, 16
    assert_draw_invariance(SEED, B, U, K, N, 1.0, 2.0,
                           rx_base=rb, u_base=ub, n_base=nb)
    g_f, z_f = fused_channels(SEED, rb + B, ub + U, K, nb + N, 1.0, 2.0)
    g_o, z_o = fused_channels(SEED, B, U, K, N, 1.0, 2.0,
                              rx_base=rb, u_base=ub, n_base=nb)
    np.testing.assert_array_equal(np.asarray(g_o),
                                  np.asarray(g_f[rb:, ub:, :, nb:]))
    np.testing.assert_array_equal(np.asarray(z_o),
                                  np.asarray(z_f[rb:, :, nb:]))


def test_fused_mac_bases_equal_tile_of_full_call():
    """`fused_mac` over an (rx, n) tile with the tile origin as counter
    bases is BITWISE the matching tile of the full-range call (same
    u/k block order per output element; symbols are independent)."""
    rng = np.random.default_rng(5)
    B, U, K, N = 4, 12, 8, 640
    t_re, t_im, amp, w = _mk(rng, B, U, N)
    kw = dict(K=K, sigma_h2=1.0, sigma_z2=2.0, interpret=True)
    y_re, y_im = fused_mac(SEED, t_re, t_im, amp, w, **kw)
    rb, nb, bb, nn_ = 1, 256, 2, 320         # tile: rx [1:3), n [256:576)
    y2_re, y2_im = fused_mac(
        SEED, t_re[:, nb:nb + nn_], t_im[:, nb:nb + nn_],
        amp[rb:rb + bb], w[rb:rb + bb], rx_base=rb, n_base=nb, **kw)
    np.testing.assert_array_equal(np.asarray(y2_re),
                                  np.asarray(y_re[rb:rb + bb, nb:nb + nn_]))
    np.testing.assert_array_equal(np.asarray(y2_im),
                                  np.asarray(y_im[rb:rb + bb, nb:nb + nn_]))
    # the materialized reference honors the same bases
    r_re, r_im = fused_mac_ref(
        SEED, t_re[:, nb:nb + nn_], t_im[:, nb:nb + nn_],
        amp[rb:rb + bb], w[rb:rb + bb], K=K, sigma_h2=1.0, sigma_z2=2.0,
        rx_base=rb, n_base=nb)
    scale = float(jnp.abs(jax.lax.complex(r_re, r_im)).max()) + 1e-12
    assert float(jnp.abs(y2_re - r_re).max()) / scale < 1e-4
    assert float(jnp.abs(y2_im - r_im).max()) / scale < 1e-4


def test_canonical_block_u():
    """Divides M always; halves down only above the cap."""
    for m in (1, 5, 64, 1024, 4096, 3000):
        assert m % canonical_block_u(m) == 0
    assert canonical_block_u(64) == 64
    assert canonical_block_u(4096) == 1024
    assert canonical_block_u(3000) == 750
    assert canonical_block_u(4096, cap=512) == 512


def test_m5_padded_u_blocks():
    """The paper's cluster hop (C = 4 ISs hear U = C * M = 20 users,
    M = 5, K = 100) with 8-user u-blocks: the kernel pads U to 24 with
    amp = w = 0 users, which draw at their own counter indices (20..23)
    and add exact zeros — bitwise the call on explicitly padded inputs,
    even with nonzero symbols on the padded rows — and the result
    agrees with the reference and with the canonical 5-user blocking."""
    rng = np.random.default_rng(55)
    B, U, K, N = 4, 20, 100, 300
    t_re, t_im, amp, w = _mk(rng, B, U, N)
    kw = dict(K=K, sigma_h2=1.0, sigma_z2=10.0)
    y_re, y_im = fused_mac(SEED, t_re, t_im, amp, w, block_u=8,
                           interpret=True, **kw)

    junk = jnp.asarray(rng.standard_normal((2, 4, N)), jnp.float32)
    zeros = jnp.zeros((B, 4), jnp.float32)
    p_re, p_im = fused_mac(
        SEED, jnp.concatenate([t_re, junk[0]]),
        jnp.concatenate([t_im, junk[1]]),
        jnp.concatenate([amp, zeros], axis=1),
        jnp.concatenate([w, zeros], axis=1), block_u=8, interpret=True,
        **kw)
    np.testing.assert_array_equal(np.asarray(p_re), np.asarray(y_re))
    np.testing.assert_array_equal(np.asarray(p_im), np.asarray(y_im))

    rr, ri = fused_mac_ref(SEED, t_re, t_im, amp, w, **kw)
    c_re, c_im = fused_mac(SEED, t_re, t_im, amp, w,
                           block_u=canonical_block_u(5), interpret=True,
                           **kw)
    scale = float(jnp.abs(jax.lax.complex(rr, ri)).max())
    for a, b in ((y_re, rr), (y_im, ri), (c_re, rr), (c_im, ri)):
        assert float(jnp.abs(a - b).max()) / scale < 1e-4


@pytest.mark.parametrize("U,K,n_tiles,N", [
    (32, 8, 2, 256),     # aligned, 2 u-tiles
    (60, 12, 4, 130),    # padded K (12 -> 16), unaligned N, 4 u-tiles
    (8, 100, 2, 96),     # heavily padded K (100 -> 104)
])
def test_partials_pinned_fold_bitwise_equals_full_call(U, K, n_tiles, N):
    """The tentpole's kernel contract: per-u-tile partial accumulators
    (`fused_mac_partials` with each tile's `u_base`), concatenated in
    pinned global block order and folded with the separately-drawn
    noise (`fused_noise` over the padded Kp), are BITWISE the full-U
    `fused_mac` output.  The fold must run in the same jitted program
    as the partials — XLA:CPU's finalize contraction is
    context-sensitive (see `fused_partials_reduce`) — which is exactly
    the structure the u-sharded executor has."""
    rng = np.random.default_rng(U + K + N)
    B = 3
    t_re, t_im, amp, w = _mk(rng, B, U, N)
    bu = U // n_tiles
    bk = 8
    Kp = -(-K // bk) * bk
    kw = dict(K=K, sigma_h2=1.0, sigma_z2=2.0)

    @jax.jit
    def folded():
        parts = []
        for j in range(n_tiles):
            u0 = j * bu
            parts.append(fused_mac_partials(
                SEED, t_re[u0:u0 + bu], t_im[u0:u0 + bu],
                amp[:, u0:u0 + bu], w[:, u0:u0 + bu], K=K, sigma_h2=1.0,
                u_base=u0, block_u=bu, interpret=True))
        pr_re, pr_im, pm_re, pm_im = (
            jnp.concatenate([p[i] for p in parts], axis=1)
            for i in range(4))
        z_re, z_im = fused_noise(SEED, B, Kp, N, 2.0)
        return fused_partials_reduce(pr_re, pr_im, pm_re, pm_im,
                                     z_re, z_im, K=K)

    y_re, y_im = fused_mac(SEED, t_re, t_im, amp, w, block_u=bu,
                           interpret=True, **kw)
    f_re, f_im = folded()
    np.testing.assert_array_equal(np.asarray(f_re), np.asarray(y_re))
    np.testing.assert_array_equal(np.asarray(f_im), np.asarray(y_im))


def test_partials_require_aligned_u():
    rng = np.random.default_rng(0)
    t_re, t_im, amp, w = _mk(rng, 1, 12, 64)
    with pytest.raises(ValueError, match="divisible"):
        fused_mac_partials(SEED, t_re, t_im, amp, w, K=4, sigma_h2=1.0,
                           block_u=8, interpret=True)


def test_rx_stations_draw_independent_channels():
    """Two rx rows with identical amp/w must still see different
    realizations (per-rx streams), as in the paper's model."""
    rng = np.random.default_rng(11)
    t_re, t_im, _, _ = _mk(rng, 1, 4, 256)
    amp = jnp.ones((2, 4), jnp.float32)
    w = jnp.ones((2, 4), jnp.float32)
    yr, yi = fused_mac(SEED, t_re, t_im, amp, w, K=8, sigma_h2=1.0,
                       sigma_z2=1.0, interpret=True)
    assert float(jnp.abs(yr[0] - yr[1]).max()) > 0.0


def test_generator_moments():
    """Counter-PRNG normals: mean ~ 0, per-complex-entry variance ~
    sigma^2, h and z streams uncorrelated."""
    g, z = fused_channels(SEED, 1, 8, 4, 8192, 1.0, 3.0)
    n = np.concatenate([np.asarray(jnp.real(g)).ravel(),
                        np.asarray(jnp.imag(g)).ravel()])
    assert abs(n.mean()) < 4.0 / np.sqrt(n.size)
    assert abs(float((jnp.abs(g) ** 2).mean()) - 1.0) < 0.02
    assert abs(float((jnp.abs(z) ** 2).mean()) - 3.0) < 0.1
    # z is K*N of the SAME (k, n) grid as g[u=0]: uncorrelated streams
    zg = np.asarray(jnp.real(z[0])).ravel()
    g0 = np.asarray(jnp.real(g[0, 0])).ravel()
    corr = np.corrcoef(zg, g0)[0, 1]
    assert abs(corr) < 4.0 / np.sqrt(zg.size)


def test_sincos_turn24_all_angles():
    """sin / cos of every 24-bit angle 2*pi*m / 2^24 within 2e-7 of
    float64 (the polynomials reach 1.07e-7; a generic f32 sin / cos of
    the rounded angle reaches 4.1e-7)."""
    m = np.arange(2 ** 24, dtype=np.int32)
    s, c = jax.jit(_sincos_turn24)(jnp.asarray(m))
    theta = 2.0 * np.pi * m.astype(np.float64) / 2 ** 24
    assert s.dtype == c.dtype == jnp.float32
    assert np.abs(np.asarray(s, np.float64) - np.sin(theta)).max() <= 2e-7
    assert np.abs(np.asarray(c, np.float64) - np.cos(theta)).max() <= 2e-7


_TURN = 2.0 * np.pi / 2 ** 24


@pytest.mark.parametrize("m,sin,cos", [
    (0, 0.0, 1.0),
    (2 ** 22, 1.0, 0.0),
    (2 ** 23, 0.0, -1.0),
    (3 * 2 ** 22, -1.0, 0.0),
    (2 ** 21, np.sqrt(0.5), np.sqrt(0.5)),        # the reduction's edge
    (2 ** 24 - 1, -np.sin(_TURN), np.cos(_TURN)),
])
def test_sincos_turn24_quadrant_edges(m, sin, cos):
    """Quarter turns come out exact; at the edges of the reduction
    (remainder -2^21, and the last step before a full turn) the signs
    are right and the values within 2e-7.  A zero may carry either
    sign."""
    s, c = (float(v[0]) for v in
            _sincos_turn24(jnp.asarray([m], jnp.int32)))
    for got, want in ((s, sin), (c, cos)):
        if want in (-1.0, 0.0, 1.0):
            assert got == want
        else:
            assert np.sign(got) == np.sign(want)
            assert abs(got - want) <= 2e-7


def test_box_muller_matches_float64():
    """`_box_muller` on 2^20 random word pairs against a float64
    Box-Muller of the same words: within 1e-6 absolute."""
    rng = np.random.default_rng(2 ** 31 + 15)
    b0, b1 = rng.integers(0, 2 ** 32, (2, 2 ** 20), dtype=np.uint64).astype(
        np.uint32)
    n0, n1 = jax.jit(_box_muller)(jnp.asarray(b0), jnp.asarray(b1))
    r = np.sqrt(-2.0 * np.log(1.0 - (b0 >> 8).astype(np.float64) / 2 ** 24))
    theta = 2.0 * np.pi * (b1 >> 8).astype(np.float64) / 2 ** 24
    assert np.abs(np.asarray(n0, np.float64) - r * np.cos(theta)).max() < 1e-6
    assert np.abs(np.asarray(n1, np.float64) - r * np.sin(theta)).max() < 1e-6


def _primitive_names(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn.primitive.name
        for v in eqn.params.values():
            for sub in v if isinstance(v, (tuple, list)) else (v,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    yield from _primitive_names(inner)


def test_box_muller_has_no_generic_trig():
    """The angle's sin / cos come from the exact integer quadrant
    reduction: no generic `sin` / `cos` (and with them Mosaic's f32
    range reduction) may come back into the draw."""
    w = jnp.zeros((8, 128), jnp.uint32)
    names = set(_primitive_names(jax.make_jaxpr(_box_muller)(w, w).jaxpr))
    assert "log" in names and "sqrt" in names      # the walk sees the body
    assert not names & {"sin", "cos"}


@pytest.mark.slow
def test_no_slab_at_large_u():
    """U=4096, K=32, N=8192: the fused hop completes on CPU without
    materializing any [U, K, N] array (the slab would be 8 GiB in
    complex64 — it cannot exist here)."""
    U, K, N = 4096, 32, 8192
    rng = np.random.default_rng(0)
    t_re = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
    t_im = jnp.asarray(rng.standard_normal((U, N)), jnp.float32)
    amp = jnp.ones((1, U), jnp.float32)
    w = jnp.ones((1, U), jnp.float32)
    yr, yi = fused_mac(SEED, t_re, t_im, amp, w, K=K, sigma_h2=1.0,
                       sigma_z2=1.0, interpret=True)
    assert yr.shape == (1, N)
    assert bool(jnp.all(jnp.isfinite(yr))) and bool(
        jnp.all(jnp.isfinite(yi)))


@pytest.mark.tpu
def test_fused_compiled_matches_interpret():
    """On a real TPU the compiled kernel must equal the interpret path
    (same counters, same draws)."""
    rng = np.random.default_rng(1)
    t_re, t_im, amp, w = _mk(rng, 2, 8, 512)
    kw = dict(K=16, sigma_h2=1.0, sigma_z2=1.0)
    yc = fused_mac(SEED, t_re, t_im, amp, w, interpret=False, **kw)
    yi_ = fused_mac(SEED, t_re, t_im, amp, w, interpret=True, **kw)
    scale = float(jnp.abs(yc[0]).max())
    np.testing.assert_allclose(np.asarray(yc[0]), np.asarray(yi_[0]),
                               atol=1e-4 * scale)
