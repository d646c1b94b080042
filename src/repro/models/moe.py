"""Mixture-of-Experts FFN (qwen3-moe-30b-a3b, olmoe-1b-7b) with sort-based
dispatch and expert parallelism over the ``model``/``expert`` mesh axis.

Dispatch strategy (TPU-native adaptation — no CUDA-style atomics):
  1. top-k routing per token;
  2. assignments sorted by expert id (argsort — XLA lowers to a parallel
     bitonic sort), rank-within-expert computed from sorted offsets;
  3. tokens gathered into a dense [E, capacity, d] block (capacity-dropped,
     as in Switch/GShard), expert-sharded grouped matmul via einsum;
  4. results scattered back and combined with router gates.

The load-balancing auxiliary loss follows Switch: E * sum_e(f_e * p_e).
The per-expert load counters that coordination-avoidance cares about
(planner: G-counters, merged at log boundaries) are returned as metrics.
"""

from __future__ import annotations

from typing import NamedTuple

import jax
import jax.numpy as jnp


from . import layers as L
from .config import ModelConfig
from .sharding import Rules

Array = jax.Array


def moe_init(rng, cfg: ModelConfig) -> dict:
    d, E, ff = cfg.d_model, cfg.n_experts, cfg.ffn_width()
    k1, k2, k3, k4 = jax.random.split(rng, 4)
    s_in, s_out = d ** -0.5, ff ** -0.5
    return {
        "router": (jax.random.normal(k1, (d, E)) * s_in).astype(jnp.float32),
        "w1": (jax.random.normal(k2, (E, d, ff)) * s_in).astype(jnp.float32),
        "w3": (jax.random.normal(k3, (E, d, ff)) * s_in).astype(jnp.float32),
        "w2": (jax.random.normal(k4, (E, ff, d)) * s_out).astype(jnp.float32),
    }


class MoEStats(NamedTuple):
    aux_loss: Array      # scalar load-balance loss
    expert_load: Array   # [E] tokens routed per expert (G-counter material)
    dropped: Array       # scalar dropped-assignment count


def _dispatch_ffn(params: dict, xf: Array, cfg: ModelConfig, cap: int
                  ) -> tuple[Array, Array, Array, Array]:
    """Core routed FFN over a flat token block xf: [T, d].

    Returns (out [T,d], aux scalar, load [E], dropped scalar). The caller
    chooses the block granularity (global vs per-sequence) — see moe_apply.
    """
    T, d = xf.shape
    E, k = cfg.n_experts, cfg.top_k

    # ---- routing -----------------------------------------------------------
    router_logits = jnp.einsum("td,de->te", xf.astype(jnp.float32),
                               params["router"])
    probs = jax.nn.softmax(router_logits, axis=-1)          # [T, E]
    gate_vals, experts = jax.lax.top_k(probs, k)             # [T, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # ---- aux loss (Switch): E * sum_e fraction_e * prob_e ------------------
    one_hot_top1 = jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32)
    fraction = one_hot_top1.mean(0)
    mean_prob = probs.mean(0)
    aux = E * jnp.sum(fraction * mean_prob) * cfg.router_aux_coef

    # ---- sort-based dispatch -----------------------------------------------
    A = T * k
    flat_expert = experts.reshape(A)
    flat_token = jnp.repeat(jnp.arange(T), k)
    flat_gate = gate_vals.reshape(A)

    order = jnp.argsort(flat_expert)                         # [A]
    sorted_e = flat_expert[order]
    # offset of each expert's first assignment in the sorted order
    first = jnp.searchsorted(sorted_e, jnp.arange(E))        # [E]
    rank = jnp.arange(A) - first[sorted_e]                   # rank within expert

    keep = rank < cap
    slot = jnp.where(keep, sorted_e * cap + rank, E * cap)   # overflow slot
    src_token = flat_token[order]

    # gather tokens into expert blocks (one dummy overflow row)
    xg = jnp.zeros((E * cap + 1, d), xf.dtype).at[slot].set(xf[src_token])
    xg = xg[:-1].reshape(E, cap, d)

    # ---- expert FFN (grouped matmul, expert-sharded) ------------------------
    w1 = params["w1"].astype(xf.dtype)
    w3 = params["w3"].astype(xf.dtype)
    w2 = params["w2"].astype(xf.dtype)
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w1)) * \
        jnp.einsum("ecd,edf->ecf", xg, w3)
    y = jnp.einsum("ecf,efd->ecd", h, w2)

    # ---- combine back --------------------------------------------------------
    yf = y.reshape(E * cap, d)
    y_sorted = jnp.where(keep[:, None],
                         yf[jnp.minimum(slot, E * cap - 1)], 0.0)
    gates_sorted = flat_gate[order]
    out = jnp.zeros((T, d), xf.dtype).at[src_token].add(
        y_sorted * gates_sorted[:, None].astype(xf.dtype))

    load = jnp.zeros((E,), jnp.int32).at[flat_expert].add(1)
    return out, aux, load, jnp.sum(~keep).astype(jnp.int32)


def moe_apply(params: dict, x: Array, cfg: ModelConfig, rules: Rules
              ) -> tuple[Array, MoEStats]:
    """x: [B, S, d] -> ([B, S, d], stats).

    Two dispatch granularities (cfg.moe_block_dispatch):

    * global (baseline): one sort/scatter over all B*S tokens. Correct, but
      the token dim of the scatter is sharded over (pod, data) while slots
      are expert-major — XLA SPMD must materialize REPLICATED dispatch
      buffers ([E*cap, d] at global capacity), exploding the memory and
      collective terms (the dominant cost of the MoE train cells in the
      baseline roofline table).
    * block-local (optimized): dispatch independently per sequence (vmap over
      the batch dim, which stays sharded over pod/data), capacity k*S*cf/E
      per block. Every dispatch op keeps the leading dim sharded; experts
      remain sharded over the expert axis, and the only cross-device traffic
      is the expert-dim contraction itself. Statistically this is per-
      sequence capacity dropping (standard in GShard-style systems).
    """
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k

    if cfg.moe_block_dispatch and B > 1:
        cap = int(max(1, round(cfg.capacity_factor * S * k / E)))
        x = rules.act(x, "batch", None, None)
        out, aux, load, dropped = jax.vmap(
            lambda xb: _dispatch_ffn(params, xb, cfg, cap))(x)
        out = rules.act(out, "batch", None, None)
        stats = MoEStats(aux_loss=aux.mean(), expert_load=load.sum(0),
                         dropped=dropped.sum())
        return out, stats

    T = B * S
    cap = int(max(1, round(cfg.capacity_factor * T * k / E)))
    out, aux, load, dropped = _dispatch_ffn(params, x.reshape(T, d), cfg, cap)
    return out.reshape(B, S, d), MoEStats(aux, load, dropped)


# ---------------------------------------------------------------------------
# MoE decoder (dense attention + MoE FFN)
# ---------------------------------------------------------------------------


def layer_init(rng, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(rng)
    return {
        "attn_norm": L.rmsnorm_init(cfg.d_model),
        "attn": L.attention_init(k1, cfg),
        "mlp_norm": L.rmsnorm_init(cfg.d_model),
        "moe": moe_init(k2, cfg),
    }


def init_params(rng, cfg: ModelConfig) -> dict:
    k_emb, k_layers = jax.random.split(rng)
    layer_keys = jax.random.split(k_layers, cfg.n_layers)
    params = L.embedding_init(k_emb, cfg)
    params["layers"] = jax.vmap(lambda kk: layer_init(kk, cfg))(layer_keys)
    params["final_norm"] = L.rmsnorm_init(cfg.d_model)
    return params


def moe_ffn(params: dict, x: Array, cfg: ModelConfig, rules: Rules):
    # dispatch chooser: explicit all-to-all EP when cfg.moe_a2a (and a mesh
    # with an expert axis is in context), else blocked/global dispatch
    if cfg.moe_a2a:
        return moe_apply_a2a(params, x, cfg, rules)
    return moe_apply(params, x, cfg, rules)


def layer_apply(lp: dict, x: Array, cfg: ModelConfig, rules: Rules,
                positions: Array, use_flash: bool) -> tuple[Array, Array]:
    h = L.attention_apply(lp["attn"], L.rmsnorm(lp["attn_norm"], x, cfg.norm_eps),
                          cfg, rules, positions, causal=True,
                          use_flash=use_flash)
    x = x + h
    h, stats = moe_ffn(lp["moe"], L.rmsnorm(lp["mlp_norm"], x, cfg.norm_eps),
                       cfg, rules)
    return x + h, stats.aux_loss


def forward(params: dict, tokens: Array, cfg: ModelConfig, rules: Rules,
            use_flash: bool = False, remat: bool = True,
            last_only: bool = False) -> tuple[Array, Array]:
    """Returns (logits, total aux loss)."""
    B, S = tokens.shape
    x = L.embed(params, tokens, cfg, rules)
    positions = jnp.arange(S)

    def apply_one(carry, lp):
        return layer_apply(lp, carry, cfg, rules, positions, use_flash)

    if remat:
        apply_one = jax.checkpoint(
            apply_one, policy=jax.checkpoint_policies.nothing_saveable)

    x, aux = jax.lax.scan(apply_one, x, params["layers"])
    if last_only:
        x = x[:, -1:]
    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    return L.logits(params, x, cfg, rules), aux.sum()


def loss_fn(params: dict, batch: dict, cfg: ModelConfig, rules: Rules,
            use_flash: bool = False, remat: bool = True) -> Array:
    lg, aux = forward(params, batch["tokens"], cfg, rules, use_flash, remat)
    return L.cross_entropy(lg, batch["labels"]) + aux


# -- serving: reuse the dense attention cache; MoE runs per decode token -----


def decode_step(params: dict, cache, token: Array, cfg: ModelConfig,
                rules: Rules):
    from . import kv_cache as kvc

    B = token.shape[0]
    x = L.embed(params, token[:, None], cfg, rules)
    pos = cache.pos
    has_scale = cache.k_scale is not None

    # attention identical to dense; FFN swapped for MoE
    def _decode_layer_moe(lp, layer_kv, xx):
        hd = cfg.resolved_head_dim()
        H, KV = cfg.n_heads, cfg.n_kv_heads
        xa = L.rmsnorm(lp["attn_norm"], xx, cfg.norm_eps)
        q = L._proj(xa, lp["attn"]["wq"], lp["attn"].get("wq_b")).reshape(B, 1, H, hd)
        k = L._proj(xa, lp["attn"]["wk"], lp["attn"].get("wk_b")).reshape(B, 1, KV, hd)
        v = L._proj(xa, lp["attn"]["wv"], lp["attn"].get("wv_b")).reshape(B, 1, KV, hd)
        q = L.apply_rope(q, pos[None, None], cfg.rope_theta)
        k = L.apply_rope(k, pos[None, None], cfg.rope_theta)
        layer_kv = kvc.write(layer_kv, k, v, pos)
        k_all, v_all = kvc.read(layer_kv, xx.dtype)
        cap = k_all.shape[1]
        slots = jnp.arange(cap)
        valid = slots < jnp.minimum(pos + 1, cap)
        kv_mask = jnp.broadcast_to(valid[None], (B, cap))
        out = L.attend(q, k_all, v_all, pos[None], slots, causal=False,
                       kv_mask=kv_mask)
        h = jnp.einsum("bsf,fd->bsd", out.reshape(B, 1, H * hd),
                       lp["attn"]["wo"].astype(xx.dtype))
        xx = xx + h
        h, _ = moe_ffn(lp["moe"], L.rmsnorm(lp["mlp_norm"], xx, cfg.norm_eps),
                       cfg, rules)
        return xx + h, layer_kv

    if has_scale:
        def body(carry, xs):
            lp, lk, lv, lks, lvs = xs
            y, lkv = _decode_layer_moe(lp, kvc.LayerKV(lk, lv, lks, lvs), carry)
            return y, (lkv.k, lkv.v, lkv.k_scale, lkv.v_scale)
        x, (nk, nv, nks, nvs) = jax.lax.scan(
            body, x, (params["layers"], cache.k, cache.v,
                      cache.k_scale, cache.v_scale))
        new_cache = kvc.KVCache(nk, nv, nks, nvs, pos + 1)
    else:
        def body(carry, xs):
            lp, lk, lv = xs
            y, lkv = _decode_layer_moe(lp, kvc.LayerKV(lk, lv, None, None), carry)
            return y, (lkv.k, lkv.v)
        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
        new_cache = kvc.KVCache(nk, nv, None, None, pos + 1)

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    lg = L.logits(params, x, cfg, rules)[:, 0]
    return lg, new_cache


def prefill(params: dict, tokens: Array, cfg: ModelConfig, rules: Rules,
            capacity=None, use_flash: bool = False):
    from . import kv_cache as kvc

    B, S = tokens.shape
    cap = capacity or S
    cache = kvc.make_cache(cfg, cfg.n_layers, B, cap)
    x = L.embed(params, tokens, cfg, rules)
    positions = jnp.arange(S)
    hd = cfg.resolved_head_dim()
    H, KV = cfg.n_heads, cfg.n_kv_heads
    has_scale = cache.k_scale is not None

    def layer_prefill(carry, lp, lk, lv, lks, lvs):
        xa = L.rmsnorm(lp["attn_norm"], carry, cfg.norm_eps)
        q = L._proj(xa, lp["attn"]["wq"], lp["attn"].get("wq_b")).reshape(B, S, H, hd)
        k = L._proj(xa, lp["attn"]["wk"], lp["attn"].get("wk_b")).reshape(B, S, KV, hd)
        v = L._proj(xa, lp["attn"]["wv"], lp["attn"].get("wv_b")).reshape(B, S, KV, hd)
        q = L.apply_rope(q, positions, cfg.rope_theta)
        k = L.apply_rope(k, positions, cfg.rope_theta)
        layer_kv = kvc.write(kvc.LayerKV(lk, lv, lks, lvs), k, v,
                             jnp.asarray(0, jnp.int32))
        out = L.attend(q, k, v, positions, positions, causal=True,
                       use_flash=use_flash, impl=cfg.attn_impl,
                       block_k=cfg.attn_block_k)
        h = jnp.einsum("bsf,fd->bsd", out.reshape(B, S, H * hd),
                       lp["attn"]["wo"].astype(carry.dtype))
        x2 = carry + h
        h, _ = moe_ffn(lp["moe"], L.rmsnorm(lp["mlp_norm"], x2, cfg.norm_eps),
                       cfg, rules)
        return x2 + h, layer_kv

    if has_scale:
        def body(carry, xs):
            lp, lk, lv, lks, lvs = xs
            y, lkv = layer_prefill(carry, lp, lk, lv, lks, lvs)
            return y, (lkv.k, lkv.v, lkv.k_scale, lkv.v_scale)
        x, (nk, nv, nks, nvs) = jax.lax.scan(
            body, x, (params["layers"], cache.k, cache.v,
                      cache.k_scale, cache.v_scale))
        cache = kvc.KVCache(nk, nv, nks, nvs, jnp.asarray(S, jnp.int32))
    else:
        def body(carry, xs):
            lp, lk, lv = xs
            y, lkv = layer_prefill(carry, lp, lk, lv, None, None)
            return y, (lkv.k, lkv.v)
        x, (nk, nv) = jax.lax.scan(body, x, (params["layers"], cache.k, cache.v))
        cache = kvc.KVCache(nk, nv, None, None, jnp.asarray(S, jnp.int32))

    x = L.rmsnorm(params["final_norm"], x, cfg.norm_eps)
    lg = L.logits(params, x[:, -1:], cfg, rules)[:, 0]
    return lg, cache


# ---------------------------------------------------------------------------
# Explicit all-to-all expert parallelism (shard_map; the EP lever of
# EXPERIMENTS.md §Perf cell A's residual analysis).
#
# Tokens are sharded over the batch axes, experts over the expert axis.
# Instead of letting auto-SPMD reshard the dispatch buffers (which gathers
# activations), each device routes its own tokens, packs per-destination
# send buffers, and a single all-to-all along the expert axis moves ONLY the
# routed tokens (~k/E-weighted traffic) there and back.
# ---------------------------------------------------------------------------


def _pack_by_key(x2d, keys, n_buckets, cap):
    """Sort rows by bucket key and scatter into [n_buckets, cap, d] with
    rank-based capacity dropping. Returns (buf, slot_of_row, keep_mask)."""
    A = keys.shape[0]
    order = jnp.argsort(keys)
    sorted_k = keys[order]
    first = jnp.searchsorted(sorted_k, jnp.arange(n_buckets))
    rank = jnp.arange(A) - first[sorted_k]
    keep = (rank < cap) & (sorted_k >= 0) & (sorted_k < n_buckets)
    slot_sorted = jnp.where(keep, sorted_k * cap + rank, n_buckets * cap)
    buf = jnp.zeros((n_buckets * cap + 1, x2d.shape[1]), x2d.dtype)
    buf = buf.at[slot_sorted].set(x2d[order])
    # slot for each ORIGINAL row (inverse permutation)
    slot_of_row = jnp.zeros((A,), jnp.int32).at[order].set(
        slot_sorted.astype(jnp.int32))
    keep_of_row = jnp.zeros((A,), jnp.bool_).at[order].set(keep)
    return buf[:-1].reshape(n_buckets, cap, x2d.shape[1]), slot_of_row, keep_of_row


def moe_apply_a2a(params: dict, x: Array, cfg: ModelConfig, rules: Rules
                  ) -> tuple[Array, MoEStats]:
    """Expert-parallel MoE with explicit all-to-all token exchange.

    Requires a mesh in context (jax.set_mesh) with the rules' batch and
    expert axes; falls back to blocked dispatch when the expert axis is
    absent or sized 1.
    """
    mesh = jax.sharding.get_abstract_mesh()
    expert_axis = rules.expert
    if (not rules.enabled or expert_axis is None
            or mesh.empty or expert_axis not in mesh.shape
            or mesh.shape[expert_axis] == 1):
        return moe_apply(params, x, cfg, rules)

    from jax.sharding import PartitionSpec as P

    batch_axes = tuple(a for a in (rules.batch or ()) if a in mesh.shape)
    n_cols = mesh.shape[expert_axis]
    E, k = cfg.n_experts, cfg.top_k
    assert E % n_cols == 0, (E, n_cols)
    e_loc = E // n_cols

    manual = set(batch_axes) | {expert_axis}

    def body(w_router, w1, w3, w2, xb):
        B_loc, S, d = xb.shape
        T = B_loc * S
        xf = xb.reshape(T, d)

        logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), w_router)
        probs = jax.nn.softmax(logits, axis=-1)
        gate_vals, experts = jax.lax.top_k(probs, k)
        gate_vals = gate_vals / jnp.maximum(
            gate_vals.sum(-1, keepdims=True), 1e-9)

        one_hot_top1 = jax.nn.one_hot(experts[:, 0], E, dtype=jnp.float32)
        aux = E * jnp.sum(one_hot_top1.mean(0) * probs.mean(0)) \
            * cfg.router_aux_coef

        A = T * k
        flat_e = experts.reshape(A)
        flat_token = jnp.repeat(jnp.arange(T), k)
        flat_gate = gate_vals.reshape(A)
        dst = flat_e // e_loc                       # destination column

        cap_send = int(max(1, round(cfg.capacity_factor * A / n_cols)))
        # payload rows carry the token vector; the local expert id and a
        # validity flag ride along as fused extra columns
        payload = jnp.concatenate(
            [xf[flat_token],
             (flat_e % e_loc).astype(xf.dtype)[:, None],
             jnp.ones((A, 1), xf.dtype)], axis=1)
        send, slot_of_row, keep_row = _pack_by_key(payload, dst, n_cols,
                                                   cap_send)

        recv = jax.lax.all_to_all(send, expert_axis, split_axis=0,
                                  concat_axis=0, tiled=False)
        # recv: [n_cols(src), cap_send, d+2] -> all rows target local experts
        rflat = recv.reshape(n_cols * cap_send, d + 2)
        r_x = rflat[:, :d]
        r_e_loc = jnp.round(rflat[:, d].astype(jnp.float32)).astype(jnp.int32)
        r_e_loc = jnp.clip(r_e_loc, 0, e_loc - 1)
        r_valid = rflat[:, d + 1] > 0.5

        cap_e = int(max(1, round(cfg.capacity_factor * n_cols * cap_send
                                 / e_loc)))
        key = jnp.where(r_valid, r_e_loc, e_loc)     # invalid -> dropped
        xg, slot_of_recv, keep_recv = _pack_by_key(r_x, key, e_loc, cap_e)

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xg, w1)) * \
            jnp.einsum("ecd,edf->ecf", xg, w3)
        y = jnp.einsum("ecf,efd->ecd", h, w2).reshape(e_loc * cap_e, d)

        # unpack expert outputs back to recv positions, then inverse a2a
        y_recv = jnp.where(
            keep_recv[:, None],
            y[jnp.minimum(slot_of_recv, e_loc * cap_e - 1)], 0.0)
        y_send = jax.lax.all_to_all(
            y_recv.reshape(n_cols, cap_send, d), expert_axis,
            split_axis=0, concat_axis=0, tiled=False)
        y_flat = y_send.reshape(n_cols * cap_send, d)

        y_rows = jnp.where(keep_row[:, None],
                           y_flat[jnp.minimum(slot_of_row,
                                              n_cols * cap_send - 1)], 0.0)
        out = jnp.zeros((T, d), xb.dtype).at[flat_token].add(
            y_rows * flat_gate[:, None].astype(xb.dtype))

        load = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
        dropped = jnp.sum(~keep_row).astype(jnp.int32)
        # stats are per-data-shard partials; reduce over the batch axes so
        # the replicated out_specs are truthful (tiny collectives)
        for a in batch_axes:
            aux = jax.lax.pmean(aux, a)
            load = jax.lax.psum(load, a)
            dropped = jax.lax.psum(dropped, a)
        return (out.reshape(B_loc, S, d), aux, load, dropped)

    sm = jax.shard_map(
        body,
        in_specs=(P(), P(expert_axis, None, None), P(expert_axis, None, None),
                  P(expert_axis, None, None), P(batch_axes, None, None)),
        out_specs=(P(batch_axes, None, None), P(), P(), P()),
        axis_names=manual, check_vma=False)

    out, aux, load, dropped = sm(params["router"],
                                 params["w1"].astype(x.dtype),
                                 params["w3"].astype(x.dtype),
                                 params["w2"].astype(x.dtype), x)
    return out, MoEStats(aux, load, dropped)
