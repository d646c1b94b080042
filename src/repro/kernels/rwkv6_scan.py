"""Pallas TPU kernel for the RWKV-6 chunked WKV scan (data-dependent decay).

Grid: (B*H, n_chunks) with the chunk dimension sequential ("arbitrary") — the
[hd, hd] recurrent state lives in VMEM scratch across chunk steps, so the HBM
traffic per chunk is exactly the r/k/v/w tiles plus the output tile (the
state never round-trips to HBM, the core win over a naive scan).

Within a chunk everything is dense [C, hd] / [C, C] math on the MXU/VPU:
  out_i = (r_i * Π_{t<i} w_t) @ S_in
        + Σ_{j<i} (Σ_k r_i k_j Π_{j<t<i} w_t) v_j
        + (r_i · (u * k_i)) v_i
  S_out = diag(Π w) S_in + Σ_j (k_j Π_{t>j} w_t)^T v_j
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu



def _rwkv_kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
                 o_ref, sT_ref, state_ref, *, chunk: int, num_chunks: int):
    ci = pl.program_id(1)

    @pl.when(ci == 0)
    def _init():
        state_ref[...] = s0_ref[0].astype(jnp.float32)

    r = r_ref[0].astype(jnp.float32)     # [C, hd]
    k = k_ref[0].astype(jnp.float32)
    v = v_ref[0].astype(jnp.float32)
    w = w_ref[0].astype(jnp.float32)
    u = u_ref[0].astype(jnp.float32)     # [hd]
    s = state_ref[...]                   # [hd, hd]

    logw = jnp.log(jnp.maximum(w, 1e-9))
    cum = jnp.cumsum(logw, axis=0)       # [C, hd]
    total = cum[-1]                      # [hd]

    d_in = jnp.exp(cum - logw)           # Π_{t<i} w_t
    out = jax.lax.dot_general(r * d_in, s, (((1,), (0,)), ((), ())),
                              preferred_element_type=jnp.float32)  # [C, hd]

    # pairwise intra-chunk decays, masked inside the exp (no inf*0)
    C = chunk
    rows = jax.lax.broadcasted_iota(jnp.int32, (C, C), 0)
    cols = jax.lax.broadcasted_iota(jnp.int32, (C, C), 1)
    strict = rows > cols
    diff = (cum - logw)[:, None, :] - cum[None, :, :]      # [C, C, hd]
    a = jnp.exp(jnp.where(strict[..., None], diff, -jnp.inf))
    scores = jnp.einsum("ik,jk,ijk->ij", r, k, a)          # [C, C]
    out = out + jax.lax.dot_general(scores, v, (((1,), (0,)), ((), ())),
                                    preferred_element_type=jnp.float32)

    cur = jnp.sum(r * (u[None] * k), axis=1)               # [C]
    out = out + cur[:, None] * v
    o_ref[0] = out.astype(o_ref.dtype)

    k_dec = k * jnp.exp(total[None] - cum)                  # Π_{t>j} w_t
    state_ref[...] = s * jnp.exp(total)[:, None] + jax.lax.dot_general(
        k_dec, v, (((0,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)

    @pl.when(ci == num_chunks - 1)
    def _finalize():
        sT_ref[0] = state_ref[...]


def rwkv6_scan_kernel(r, k, v, w, u, s0, *, chunk: int = 64,
                      interpret: bool = False):
    """r/k/v/w: [B,T,H,hd]; u: [H,hd]; s0: [B,H,hd,hd].

    Returns (out [B,T,H,hd], s_T [B,H,hd,hd]).
    """
    B, T, H, hd = r.shape
    C = min(chunk, T)
    assert T % C == 0, (T, C)
    n = T // C

    # head-major: [B*H, T, hd]; state [B*H, hd, hd]
    def hm(x):
        return x.transpose(0, 2, 1, 3).reshape(B * H, T, hd)

    rh, kh, vh, wh = hm(r), hm(k), hm(v), hm(w)
    sh = s0.reshape(B * H, hd, hd)

    out, sT = pl.pallas_call(
        functools.partial(_rwkv_kernel, chunk=C, num_chunks=n),
        grid=(B * H, n),
        in_specs=[
            pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd), lambda b, c: (b % H, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_specs=[
            pl.BlockSpec((1, C, hd), lambda b, c: (b, c, 0)),
            pl.BlockSpec((1, hd, hd), lambda b, c: (b, 0, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((B * H, T, hd), r.dtype),
            jax.ShapeDtypeStruct((B * H, hd, hd), jnp.float32),
        ],
        scratch_shapes=[pltpu.VMEM((hd, hd), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(rh, kh, vh, wh, u, sh)

    out = out.reshape(B, H, T, hd).transpose(0, 2, 1, 3)
    return out, sT.reshape(B, H, hd, hd)
