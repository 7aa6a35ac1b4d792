"""Pallas TPU flash-attention kernel (causal, GQA via group folding).

The §Roofline analysis shows attention *score materialization* is the
dominant HBM term of every dense train/prefill pair (e.g. qwen2-1.5b
train_4k: ~70% of 24 TB/step/device).  The XLA-level fix
(`attn_impl="online"`, nn/attention.py) blocks the KV axis with a
running-max recurrence; this kernel is the TPU-native version: the
[QB, KB] score tile lives only in VMEM, with the online-softmax
accumulator (acc, m, l) in VMEM scratch across the KB grid dimension.

TPU adaptation notes:
- tiles QB x KB chosen so q-tile, k-tile, v-tile and the score tile fit
  VMEM with MXU-aligned dims (multiples of 128 lanes / 8 sublanes);
- GQA: the G query heads per KV head are folded into the q row axis
  (callers use `flash_attention` below), so the kernel itself is MHA
  with heads folded into the grid's batch dimension — no gather needed;
- causal masking is computed from block indices (no [L, S] mask tensor
  in HBM at all);
- fully-masked (future) KV blocks are skipped via `pl.when` on the
  block index comparison — the causal lower triangle does ~half the
  tiles' work, matching the 2x flash-attention speedup on TPU.

Validated against `ref.flash_attention_ref` (pure jnp, same fold) in
interpret mode over shape sweeps (tests/test_flash_attn.py).
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _flash_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                  scale: float, lq: int, causal: bool):
    """Grid (N, nQ, nK), K minor. Blocks: q [QB, hd], k/v [KB, hd],
    o [QB, hd]; scratch acc [QB, hd] f32, m/l [QB, 128] f32."""
    qi, ki = pl.program_id(1), pl.program_id(2)
    QB, hd = q_ref.shape
    KB = k_ref.shape[0]

    @pl.when(ki == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    # block-level causal skip: row positions are (global q row) % lq
    q_rows = qi * QB + jax.lax.broadcasted_iota(jnp.int32, (QB, 1), 0)
    q_pos = q_rows % lq
    k_pos = ki * KB + jax.lax.broadcasted_iota(jnp.int32, (1, KB), 1)

    first_q_pos = (qi * QB) % lq

    @pl.when(jnp.logical_not(causal) | (ki * KB <= first_q_pos + QB - 1))
    def _compute():
        q = q_ref[...].astype(jnp.float32)
        k = k_ref[...].astype(jnp.float32)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32) * scale   # [QB, KB]
        if causal:
            s = jnp.where(k_pos <= q_pos, s, NEG_INF)
        m_prev = m_ref[:, :1]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        corr = jnp.exp(m_prev - m_new)
        e = jnp.exp(s - m_new)
        l_ref[:, :1] = l_ref[:, :1] * corr + e.sum(-1, keepdims=True)
        m_ref[:, :1] = m_new
        v = v_ref[...].astype(jnp.float32)
        acc_ref[...] = acc_ref[...] * corr + jax.lax.dot_general(
            e, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)

    @pl.when(ki == pl.num_programs(2) - 1)
    def _finalize():
        o_ref[...] = (acc_ref[...]
                      / jnp.maximum(l_ref[:, :1], 1e-30)).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("causal", "q_block", "kv_block",
                                             "interpret", "seq_len"))
def flash_mha(q, k, v, *, causal: bool = True, q_block: int = 256,
              kv_block: int = 256, interpret: bool = False,
              seq_len: int = 0):
    """q: [N, Lq, hd]; k, v: [N, S, hd] (heads folded into N).

    `seq_len` is the TRUE sequence length when the row axis folds
    multiple query heads (rows r map to position r %% seq_len); 0 means
    rows == positions.  Returns [N, Lq, hd].
    """
    N, Lq, hd = q.shape
    seq_len = seq_len or Lq
    S = k.shape[1]
    QB = min(q_block, Lq)
    KB = min(kv_block, S)
    if Lq % QB or S % KB:
        raise ValueError(f"Lq={Lq} % QB={QB} or S={S} % KB={KB} != 0")
    grid = (N, Lq // QB, S // KB)
    scale = 1.0 / math.sqrt(hd)

    kern = functools.partial(_flash_kernel, scale=scale, lq=seq_len,
                             causal=causal)
    return pl.pallas_call(
        kern,
        grid=grid,
        in_specs=[
            pl.BlockSpec((None, QB, hd), lambda n, qi, ki: (n, qi, 0)),
            pl.BlockSpec((None, KB, hd), lambda n, qi, ki: (n, ki, 0)),
            pl.BlockSpec((None, KB, hd), lambda n, qi, ki: (n, ki, 0)),
        ],
        out_specs=pl.BlockSpec((None, QB, hd), lambda n, qi, ki: (n, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((N, Lq, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((QB, hd), jnp.float32),   # acc
            pltpu.VMEM((QB, 128), jnp.float32),  # running max (lane-padded)
            pltpu.VMEM((QB, 128), jnp.float32),  # running denominator
        ],
        interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
    )(q, k, v)


def flash_attention(q, k, v, *, causal: bool = True, q_block: int = 256,
                    kv_block: int = 256, interpret: bool = False):
    """GQA wrapper. q: [B, Lq, H, hd]; k, v: [B, S, KV, hd] -> [B, Lq, H*hd].

    Folds the G = H/KV query heads per KV head into the row axis, so the
    causal structure per fold-group is preserved (Lq % q_block == 0).
    """
    B, Lq, H, hd = q.shape
    S, KV = k.shape[1], k.shape[2]
    G = H // KV
    # [B, Lq, KV, G, hd] -> [B*KV, G*Lq, hd]
    qf = (q.reshape(B, Lq, KV, G, hd).transpose(0, 2, 3, 1, 4)
          .reshape(B * KV, G * Lq, hd))
    kf = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vf = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    of = flash_mha(qf, kf, vf, causal=causal, q_block=q_block,
                   kv_block=kv_block, interpret=interpret, seq_len=Lq)
    out = (of.reshape(B, KV, G, Lq, hd).transpose(0, 3, 1, 2, 4)
           .reshape(B, Lq, H * hd))
    return out
