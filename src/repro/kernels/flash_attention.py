"""Pallas TPU flash attention (causal/full, GQA) with explicit VMEM tiling.

Grid: (batch*heads, q_blocks, k_blocks) with the k dimension iterated
sequentially ("arbitrary") so the online-softmax accumulators live in VMEM
scratch across k steps. Block shapes are MXU-aligned (multiples of 128 on the
sequence dims whenever the sequence allows; head_dim is the lane dim).

GQA is handled in the index maps: program b enumerates (batch, q-head) and
the K/V specs map it to (batch, q_head // group) — no KV replication in HBM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


NEG_INF = float(jnp.finfo(jnp.float32).min)


def _attn_kernel(q_ref, k_ref, v_ref, o_ref, acc_ref, m_ref, l_ref, *,
                 scale: float, causal: bool, block_q: int, block_k: int,
                 num_k_blocks: int):
    qi = pl.program_id(1)
    kj = pl.program_id(2)

    @pl.when(kj == 0)
    def _init():
        acc_ref[...] = jnp.zeros_like(acc_ref)
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)

    def _compute():
        q = q_ref[0].astype(jnp.float32) * scale          # [bq, hd]
        k = k_ref[0].astype(jnp.float32)                  # [bk, hd]
        v = v_ref[0].astype(jnp.float32)                  # [bk, hd]
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32)  # [bq, bk]
        if causal:
            rows = qi * block_q + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 0)
            cols = kj * block_k + jax.lax.broadcasted_iota(
                jnp.int32, (block_q, block_k), 1)
            s = jnp.where(rows >= cols, s, NEG_INF)

        m_prev = m_ref[...]
        m_cur = jnp.maximum(m_prev, s.max(axis=1))
        alpha = jnp.exp(m_prev - m_cur)
        p = jnp.exp(s - m_cur[:, None])
        l_ref[...] = l_ref[...] * alpha + p.sum(axis=1)
        acc_ref[...] = acc_ref[...] * alpha[:, None] + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_ref[...] = m_cur

    if causal:
        # skip fully-masked k blocks (above the diagonal)
        @pl.when(kj * block_k <= qi * block_q + block_q - 1)
        def _():
            _compute()
    else:
        _compute()

    @pl.when(kj == num_k_blocks - 1)
    def _finalize():
        denom = jnp.maximum(l_ref[...], 1e-30)
        o_ref[0] = (acc_ref[...] / denom[:, None]).astype(o_ref.dtype)


def flash_attention_kernel(q, k, v, *, causal: bool = True,
                           block_q: int = 128, block_k: int = 128,
                           interpret: bool = False):
    """q: [B,S,H,hd]; k/v: [B,S,KV,hd] -> [B,S,H,hd]."""
    B, S, H, hd = q.shape
    KV = k.shape[2]
    g = H // KV
    block_q = min(block_q, S)
    block_k = min(block_k, S)
    assert S % block_q == 0 and S % block_k == 0, (S, block_q, block_k)
    nq, nk = S // block_q, S // block_k

    # head-major layouts: [B*H, S, hd] and [B*KV, S, hd]
    qh = q.transpose(0, 2, 1, 3).reshape(B * H, S, hd)
    kh = k.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)
    vh = v.transpose(0, 2, 1, 3).reshape(B * KV, S, hd)

    def kv_index(b, i, j):
        return (b // H) * KV + (b % H) // g, j, 0

    out = pl.pallas_call(
        functools.partial(_attn_kernel, scale=hd ** -0.5, causal=causal,
                          block_q=block_q, block_k=block_k, num_k_blocks=nk),
        grid=(B * H, nq, nk),
        in_specs=[
            pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
            pl.BlockSpec((1, block_k, hd), kv_index),
            pl.BlockSpec((1, block_k, hd), kv_index),
        ],
        out_specs=pl.BlockSpec((1, block_q, hd), lambda b, i, j: (b, i, 0)),
        out_shape=jax.ShapeDtypeStruct((B * H, S, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q, hd), jnp.float32),   # output accumulator
            pltpu.VMEM((block_q,), jnp.float32),      # running max m
            pltpu.VMEM((block_q,), jnp.float32),      # running denom l
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qh, kh, vh)
    return out.reshape(B, H, S, hd).transpose(0, 2, 1, 3)
