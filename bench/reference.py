"""Plain reference of the W-HFL round the benchmark times.

It follows the paper's protocol (Sec. II-III) in straightforward
`jax.numpy`, written from the protocol and not from the program, and
reproduces the seeded draws the round is defined by:

- per-round keys: the carried key splits into (next, round key); the
  round key splits into I + 1 keys, one per cluster hop and one for
  the IS -> PS hop; a cluster hop's key splits into (users, channel);
- users: the users' key splits into one key per user (cluster-major);
  a user's key into `tau` step keys; a step key into (batch, dropout);
  the minibatch is `randint(batch key, [batch], 0, n)` with
  replacement;
- local steps: Adam (b1 0.9, b2 0.999, eps 1e-8) or SGD, whose step
  count is the global round index, for all `tau` steps of a round;
- OTA packing (eq. 7): the model difference, leaves concatenated in
  tree order and zero-padded to even length 2N, is sent as N complex
  symbols, the first half real and the second imaginary;
- channel "equivalent": the closed-form surrogate of eq. (11)/(19)
  with eps ~ N(0, 1/K) per symbol and complex Gaussian noise of the
  Lemma 7/9 variances;
- channel "fused" (faithful): Rayleigh fading h = sqrt(beta) g and
  receiver noise z drawn per element by a counter PRNG (threefry2x32,
  20 rounds, then Box-Muller) keyed on the hop key's two words folded
  with (receiver, stream) and counted on (u * Kstride + k, n); the
  matched filter y = sum_k conj(sum_u w_u h_u) (sum_u h_u P t_u + z),
  rescaled by 1 / (K P sigma_h^2 sum_m beta) (eq. 12/17);
- power: P_t = 1 + 0.01 t (halved when I = 1), P_IS = 20 P_t (Sec. V).

`dtype` float32 runs everything in float32 with matmuls at the highest
precision: the reference.  `dtype` bfloat16 runs every array in
bfloat16 (draws are made in float32 and rounded): the control, one
precision step below the configuration's float32.
"""
from __future__ import annotations

import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np

GOLDEN = np.uint32(0x9E3779B9)
STREAM = np.uint32(0x85EBCA77)
TAG_CHAN, TAG_NOISE = 1, 2
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8
USER_BATCH = 64          # users trained side by side


# -- counter PRNG of the faithful channel -----------------------------------

def _rotl(x, r):
    return (x << np.uint32(r)) | (x >> np.uint32(32 - r))


def threefry2x32(k0, k1, x0, x1):
    """The 20-round threefry2x32 block cipher on uint32 arrays."""
    rot = ((13, 15, 26, 6), (17, 29, 16, 24))
    ks = (k0, k1, k0 ^ k1 ^ np.uint32(0x1BD11BDA))
    x0, x1 = x0 + ks[0], x1 + ks[1]
    for i in range(5):
        for r in rot[i % 2]:
            x0 = x0 + x1
            x1 = _rotl(x1, r) ^ x0
        x0 = x0 + ks[(i + 1) % 3]
        x1 = x1 + ks[(i + 2) % 3] + np.uint32(i + 1)
    return x0, x1


def counter_normal(s0, s1, rx, tag, c0, c1, sigma):
    """(re, im), each N(0, sigma^2), of the complex draw at counter
    (c0, c1) of receiver `rx`'s stream `tag`."""
    k0 = s0 + jnp.asarray(rx, jnp.uint32) * GOLDEN
    k1 = s1 + np.uint32((tag * int(STREAM)) & 0xFFFFFFFF)
    b0, b1 = threefry2x32(k0, k1, c0, c1)
    u1 = 1.0 - (b0 >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    u2 = (b1 >> 8).astype(jnp.float32) * np.float32(2.0 ** -24)
    r = jnp.sqrt(-2.0 * jnp.log(u1))
    th = np.float32(2.0 * np.pi) * u2
    return sigma * r * jnp.cos(th), sigma * r * jnp.sin(th)


def faithful_combine(words, t_re, t_im, amp, w, *, K, sigma_h2, sigma_z2,
                     dtype, u_block):
    """y[b, n] = sum_k conj(sum_u w[b,u] h[b,u,k,n])
                 * (sum_u h[b,u,k,n] t[u,n] + z[b,k,n]),  h = amp * g.

    t [U, N] (already scaled by P), amp / w [B, U]; returns (re, im)
    [B, N].  Receivers run one at a time and users in blocks of
    `u_block`, so the live draws are [u_block, K, N]."""
    U, N = t_re.shape
    B = amp.shape[0]
    kstride = np.uint32((K + 127) // 128 * 128)
    s0, s1 = words[0], words[1]
    sh = np.float32(np.sqrt(sigma_h2 / 2.0))
    sz = np.float32(np.sqrt(sigma_z2 / 2.0))
    kk = jnp.arange(K, dtype=jnp.uint32)[:, None]
    nn = jnp.arange(N, dtype=jnp.uint32)[None, :]
    G = U // u_block
    t_re = t_re.reshape(G, u_block, N)
    t_im = t_im.reshape(G, u_block, N)

    def one_rx(b):
        z_re, z_im = counter_normal(s0, s1, b, TAG_NOISE,
                                    jnp.broadcast_to(kk, (K, N)),
                                    jnp.broadcast_to(nn, (K, N)), sz)
        a_b = amp[b].reshape(G, u_block)
        w_b = w[b].reshape(G, u_block)

        def block(acc, g):
            uu = (g * u_block + jnp.arange(u_block, dtype=jnp.uint32))
            c0 = uu[:, None, None] * kstride + kk[None]
            c1 = jnp.broadcast_to(nn[None], (u_block, K, N))
            g_re, g_im = counter_normal(s0, s1, b, TAG_CHAN,
                                        jnp.broadcast_to(c0, (u_block, K, N)),
                                        c1, sh)
            g_re, g_im = g_re.astype(dtype), g_im.astype(dtype)
            a = a_b[g][:, None, None].astype(dtype)
            wa = (w_b[g] * a_b[g])[:, None, None].astype(dtype)
            tr = t_re[g][:, None, :].astype(dtype)
            ti = t_im[g][:, None, :].astype(dtype)
            h_re, h_im = a * g_re, a * g_im
            r_re, r_im, m_re, m_im = acc
            return (r_re + jnp.sum(h_re * tr - h_im * ti, axis=0),
                    r_im + jnp.sum(h_re * ti + h_im * tr, axis=0),
                    m_re + jnp.sum(wa * g_re, axis=0),
                    m_im + jnp.sum(wa * g_im, axis=0)), None

        zero = jnp.zeros((K, N), dtype)
        (r_re, r_im, m_re, m_im), _ = jax.lax.scan(
            block, (z_re.astype(dtype), z_im.astype(dtype), zero, zero),
            jnp.arange(G, dtype=jnp.uint32))
        return (jnp.sum(m_re * r_re + m_im * r_im, axis=0),
                jnp.sum(m_re * r_im - m_im * r_re, axis=0))

    return jax.lax.map(one_rx, jnp.arange(B, dtype=jnp.uint32))


# -- the round -----------------------------------------------------------------

@dataclass(frozen=True)
class Setup:
    """Everything static about the round: sizes, optimizer, channel and
    geometry (large-scale fading beta = d ** -p)."""
    C: int
    M: int
    K: int
    K_ps: int
    tau: int
    I: int
    batch: int
    opt: str
    lr: float
    channel: str             # "equivalent" | "fused"
    sigma_h2: float
    sigma_z2: float
    power: tuple             # (base, slope, is_factor, low)
    beta_mu_is: np.ndarray   # [C, M, C]
    beta_is: np.ndarray      # [C]

    @staticmethod
    def from_config(cfg, traffic, d_mu_is, d_is_ps):
        p = cfg["path_loss"]
        pw = cfg["power"]
        return Setup(
            C=cfg["C"], M=cfg["M"], K=cfg["K"], K_ps=cfg["K_ps"],
            tau=cfg["tau"], I=cfg["I"], batch=cfg["batch"], opt=cfg["opt"],
            lr=cfg["lr"], channel=traffic["channel"],
            sigma_h2=cfg["sigma_h2"], sigma_z2=cfg["sigma_z2"],
            power=(pw["base"], pw["slope"], pw["is_factor"],
                   bool(pw["low"])),
            beta_mu_is=np.asarray(d_mu_is, np.float64) ** -p,
            beta_is=np.asarray(d_is_ps, np.float64) ** -p)


def power(setup: Setup, t: int):
    base, slope, is_factor, low = setup.power
    P = base + slope * float(t)
    if low:
        P *= 0.5
    return np.float32(P), np.float32(is_factor * P)


def flatten(tree, dtype):
    flat = jnp.concatenate([l.reshape(-1).astype(dtype)
                            for l in jax.tree.leaves(tree)])
    return jnp.pad(flat, (0, flat.shape[0] % 2))


def unflatten(like, vec):
    leaves, tdef = jax.tree.flatten(like)
    out, off = [], 0
    for l in leaves:
        out.append(vec[off:off + l.size].reshape(l.shape).astype(l.dtype))
        off += l.size
    return jax.tree.unflatten(tdef, out)


def _xent(logits, y):
    logp = jax.nn.log_softmax(logits)
    return -jnp.mean(jnp.sum(logp * jax.nn.one_hot(y, 10, dtype=logp.dtype),
                             axis=-1))


def _normal(key, shape, dtype):
    return jax.random.normal(key, shape, jnp.float32).astype(dtype)


def _cn(key, shape, dtype):
    """Unit complex normal CN(0, 1) as (re, im)."""
    kr, ki = jax.random.split(key)
    s = np.float32(np.sqrt(0.5))
    return (s * _normal(kr, shape, dtype)).astype(dtype), \
        (s * _normal(ki, shape, dtype)).astype(dtype)


def control(setup: Setup, model, fault: str = "") -> "Round":
    """The reference one precision step below the configuration's
    float32: every array in bfloat16."""
    return Round(setup, model, jnp.bfloat16, fault)


class Round:
    """One seed's W-HFL rounds under the plain reference."""

    def __init__(self, setup: Setup, model, dtype, fault: str = ""):
        """`fault` plants one fault, for reading what the comparison
        makes of it: "half_batch" (each local step's loss is the mean
        over the first half of its minibatch) or "half_test" (the eval
        loss is taken over the first half of the test set)."""
        self.s = setup
        self.model = model
        self.dt = jnp.dtype(dtype)
        self.fault = fault
        self._round = jax.jit(self._round_impl)
        self._eval = jax.jit(self._eval_impl)

    # local training --------------------------------------------------------

    def _loss(self, params, x, y, rng):
        return _xent(self.model.apply(params, x, train=True, rng=rng), y)

    def _user(self, theta, opt, x, y, key, step):
        s = self.s

        def body(carry, k):
            th, st = carry
            kb, kd = jax.random.split(k)
            idx = jax.random.randint(kb, (s.batch,), 0, x.shape[0])
            if self.fault == "half_batch":
                idx = idx[: s.batch // 2]
            g = jax.grad(self._loss)(th, x[idx], y[idx], kd)
            if s.opt == "adam":
                t = (step + 1).astype(jnp.float32)
                m = jax.tree.map(lambda m, g: ADAM_B1 * m + (1 - ADAM_B1) * g,
                                 st["m"], g)
                v = jax.tree.map(
                    lambda v, g: ADAM_B2 * v + (1 - ADAM_B2) * g * g,
                    st["v"], g)
                bc1 = (1 - jnp.power(np.float32(ADAM_B1), t)).astype(self.dt)
                bc2 = (1 - jnp.power(np.float32(ADAM_B2), t)).astype(self.dt)
                th = jax.tree.map(
                    lambda p, m, v: p - s.lr * (m / bc1)
                    / (jnp.sqrt(v / bc2) + ADAM_EPS), th, m, v)
                st = {"m": m, "v": v}
            else:
                th = jax.tree.map(lambda p, g: p - s.lr * g, th, g)
            return (th, st), g

        keys = jax.random.split(key, s.tau)
        (th, st), gs = jax.lax.scan(body, (theta, opt), keys)
        delta = jax.tree.map(lambda a, b: a - b, th, theta)
        first_grad = jax.tree.map(lambda g: g[0], gs)
        return flatten(delta, self.dt), st, first_grad

    def _users(self, theta_IS, opt, X, Y, key, step):
        """All users, one block of users at a time (cluster-major)."""
        C, M = self.s.C, self.s.M
        keys = jax.random.split(key, C * M)
        th_u = jax.tree.map(lambda a: jnp.repeat(a, M, axis=0), theta_IS)
        flat_opt = jax.tree.map(lambda a: a.reshape((C * M,) + a.shape[2:]),
                                opt)

        def one(args):
            th, st, x, y, k = args
            return self._user(th, st, x, y, k, step)

        flat, opt, g1 = jax.lax.map(
            one, (th_u, flat_opt, X.reshape((C * M,) + X.shape[2:]),
                  Y.reshape((C * M,) + Y.shape[2:]), keys),
            batch_size=min(C * M, USER_BATCH))
        unflat = lambda a: a.reshape((C, M) + a.shape[1:])
        return (flat.reshape(C, M, -1), jax.tree.map(unflat, opt),
                jax.tree.map(unflat, g1))

    # OTA hops ----------------------------------------------------------------

    def _cluster_hop(self, key, flat, P):
        s, dt = self.s, self.dt
        C, M = s.C, s.M
        N = flat.shape[-1] // 2
        t_re, t_im = flat[..., :N], flat[..., N:]          # [C, M, N]
        beta = jnp.asarray(s.beta_mu_is, jnp.float32).astype(dt)
        b_own = jnp.stack([beta[c, :, c] for c in range(C)])    # [C, M]
        bb = jnp.sum(b_own, axis=1)                              # [C]
        if s.channel == "fused":
            U = C * M
            amp = jnp.sqrt(jnp.asarray(s.beta_mu_is, jnp.float32)
                           .reshape(U, C).T)
            own = jnp.repeat(jnp.eye(C, dtype=jnp.float32), M, axis=1)
            words = jax.random.key_data(key).astype(jnp.uint32) \
                if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) else key
            y_re, y_im = faithful_combine(
                words.reshape(-1)[:2], (P * t_re).reshape(U, N),
                (P * t_im).reshape(U, N), amp, own, K=s.K,
                sigma_h2=s.sigma_h2, sigma_z2=s.sigma_z2, dtype=dt,
                u_block=M)
            scale = (1.0 / (s.K * P * s.sigma_h2 * bb))[:, None]
            return jnp.concatenate([y_re * scale, y_im * scale], axis=-1)
        # equivalent
        K = float(s.K)
        k_eps, _, k_no = jax.random.split(key, 3)
        eps = _normal(k_eps, (C, M, N), dt) / math.sqrt(K)
        one_eps = 1 + eps
        sig_re = jnp.sum(b_own[..., None] * t_re * one_eps, 1) / bb[:, None]
        sig_im = jnp.sum(b_own[..., None] * t_im * one_eps, 1) / bb[:, None]
        p2 = t_re * t_re + t_im * t_im
        w_intra = jnp.sum(b_own[..., None] * p2
                          * (bb[:, None, None] - b_own[..., None]), axis=1)
        V_intra = w_intra / (K * bb[:, None] ** 2)
        # inter: sum over every (c', m') of beta to IS c, minus own
        cross = (jnp.sum(beta[:, :, :, None] * p2[:, :, None, :],
                         axis=(0, 1))
                 - jnp.sum(b_own[..., None] * p2, axis=1))
        V_inter = bb[:, None] * cross / (K * bb[:, None] ** 2)
        V_noise = (s.sigma_z2 / ((P ** 2) * s.sigma_h2 * bb * K))[:, None]
        n_re, n_im = _cn(k_no, (C, N), dt)
        sd = jnp.sqrt(V_intra + V_inter + V_noise)
        return jnp.concatenate([sig_re + n_re * sd, sig_im + n_im * sd], -1)

    def _global_hop(self, key, is_flat, P):
        s, dt = self.s, self.dt
        C = s.C
        N = is_flat.shape[-1] // 2
        t_re, t_im = is_flat[:, :N], is_flat[:, N:]
        b = jnp.asarray(s.beta_is, jnp.float32).astype(dt)
        b_bar = jnp.sum(b)
        if s.channel == "fused":
            words = key.reshape(-1)[:2].astype(jnp.uint32)
            y_re, y_im = faithful_combine(
                words, P * t_re, P * t_im,
                jnp.sqrt(jnp.asarray(s.beta_is, jnp.float32))[None, :],
                jnp.ones((1, C), jnp.float32), K=s.K_ps,
                sigma_h2=s.sigma_h2, sigma_z2=s.sigma_z2, dtype=dt,
                u_block=C)
            scale = 1.0 / (s.K_ps * P * s.sigma_h2 * b_bar)
            return jnp.concatenate([y_re[0] * scale, y_im[0] * scale])
        K = float(s.K_ps)
        k_eps, k_no = jax.random.split(key)
        eps = _normal(k_eps, (C, N), dt) / math.sqrt(K)
        sig_re = jnp.sum(b[:, None] * t_re * (1 + eps), 0) / b_bar
        sig_im = jnp.sum(b[:, None] * t_im * (1 + eps), 0) / b_bar
        p2 = t_re * t_re + t_im * t_im
        V_int = (jnp.sum(b[:, None] * p2 * (b_bar - b)[:, None], 0)
                 / (K * b_bar ** 2))
        V_noise = s.sigma_z2 / ((P ** 2) * s.sigma_h2 * b_bar * K)
        n_re, n_im = _cn(k_no, (N,), dt)
        sd = jnp.sqrt(V_int + V_noise)
        return jnp.concatenate([sig_re + n_re * sd, sig_im + n_im * sd])

    # round -------------------------------------------------------------------

    def _round_impl(self, theta, opt, X, Y, key, step, P, P_is):
        s = self.s
        P, P_is = P.astype(self.dt), P_is.astype(self.dt)
        keys = jax.random.split(key, s.I + 1)
        theta_IS = jax.tree.map(
            lambda a: jnp.broadcast_to(a, (s.C,) + a.shape), theta)
        g_first = None
        for i in range(s.I):
            k1, k2 = jax.random.split(keys[i])
            flat, opt, g1 = self._users(theta_IS, opt, X, Y, k1, step)
            g_first = g1 if g_first is None else g_first
            est = self._cluster_hop(k2, flat, P)
            theta_IS = jax.vmap(lambda th, e: jax.tree.map(
                lambda a, b: a + b, th, unflatten(th, e)))(theta_IS, est)
        is_flat = jax.vmap(lambda th: flatten(
            jax.tree.map(lambda a, b: a - b, th, theta), self.dt))(theta_IS)
        est = self._global_hop(keys[-1], is_flat, P_is)
        theta = jax.tree.map(lambda a, b: a + b, theta,
                             unflatten(theta, est))
        return theta, opt, g_first

    def _eval_impl(self, theta, xte, yte):
        if self.fault == "half_test":
            xte, yte = xte[: len(xte) // 2], yte[: len(yte) // 2]
        return _xent(self.model.apply(theta, xte.astype(self.dt)), yte)

    def run(self, theta, X, Y, xte, yte, key, n_rounds):
        """Rounds 0 .. n_rounds - 1 from `theta` (fresh optimizer state)
        and the seed's carried `key`.  Returns the eval loss after each
        round, the optimizer state after round 1, every user's first
        gradient of round 1, and theta after the last round."""
        s, dt = self.s, self.dt
        cast = lambda a: jnp.asarray(a).astype(dt)
        theta = jax.tree.map(cast, theta)
        X = cast(X)
        if s.opt == "adam":
            zeros = jax.tree.map(
                lambda a: jnp.zeros((s.C, s.M) + a.shape, dt), theta)
            opt = {"m": zeros, "v": zeros}
        else:
            opt = ()
        losses, opt1, g1 = [], None, None
        with jax.default_matmul_precision("highest"):
            for t in range(n_rounds):
                key, sub = jax.random.split(key)
                P, P_is = power(s, t)
                theta, opt, g = self._round(theta, opt, X, Y, sub,
                                            jnp.int32(t), P, P_is)
                losses.append(float(self._eval(theta, xte, yte)))
                if t == 0:
                    opt1, g1 = jax.device_get((opt, g))
        return losses, opt1, g1, jax.device_get(theta)
