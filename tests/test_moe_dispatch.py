"""Perf-variant correctness: blocked MoE dispatch and chunked attention must
match their baselines (the §Perf optimizations never trade correctness)."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.configs import registry
from repro.models import layers as L
from repro.models import moe
from repro.models.sharding import Rules

RULES = Rules.disabled()


def test_blocked_dispatch_matches_global_when_capacity_permits():
    cfg0 = registry.get_config("olmoe-1b-7b").reduced()
    cfg_g = dataclasses.replace(cfg0, capacity_factor=16.0)
    cfg_b = dataclasses.replace(cfg0, capacity_factor=16.0,
                                moe_block_dispatch=True)
    params = registry.init_params(jax.random.PRNGKey(0), cfg_g)
    toks = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg_g.vocab)
    lg_g, _ = moe.forward(params, toks, cfg_g, RULES, remat=False)
    lg_b, _ = moe.forward(params, toks, cfg_b, RULES, remat=False)
    np.testing.assert_allclose(np.asarray(lg_g), np.asarray(lg_b),
                               rtol=2e-4, atol=2e-4)


def test_blocked_dispatch_trains():
    cfg = dataclasses.replace(registry.get_config("qwen3-moe-30b-a3b").reduced(),
                              moe_block_dispatch=True)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    batch = registry.make_train_batch(jax.random.PRNGKey(1), cfg, 4, 16)
    loss_fn = registry.make_loss_fn(cfg, RULES, remat=False)
    l1, g = jax.value_and_grad(loss_fn)(params, batch)
    assert jnp.isfinite(l1)
    params2 = jax.tree.map(lambda p, gg: p - 0.3 * gg, params, g)
    l2 = loss_fn(params2, batch)
    assert float(l2) < float(l1)


def test_blocked_dispatch_load_stats():
    cfg = dataclasses.replace(registry.get_config("olmoe-1b-7b").reduced(),
                              moe_block_dispatch=True)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg.d_model))
    out, stats = moe.moe_apply(params["layers"]["moe"] if False else
                               jax.tree.map(lambda p: p[0],
                                            params["layers"])["moe"],
                               x, cfg, RULES)
    # every assignment counted exactly once across blocks
    assert int(stats.expert_load.sum()) == 4 * 16 * cfg.top_k


@pytest.mark.parametrize("causal,window", [(True, 0), (False, 0), (True, 16)])
def test_chunked_attention_matches_naive(causal, window):
    B, S, H, KV, hd = 2, 64, 4, 2, 16
    ks = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S)
    o1 = L.attend(q, k, v, pos, pos, causal=causal, window=window,
                  impl="naive")
    o2 = L.attend(q, k, v, pos, pos, causal=causal, window=window,
                  impl="chunked", block_k=16)
    np.testing.assert_allclose(np.asarray(o1), np.asarray(o2),
                               rtol=2e-5, atol=2e-5)


def test_chunked_attention_gradients_match():
    B, S, H, KV, hd = 1, 32, 2, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(3), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, S, KV, hd))
    v = jax.random.normal(ks[2], (B, S, KV, hd))
    pos = jnp.arange(S)

    def f(impl):
        def loss(qq, kk, vv):
            return L.attend(qq, kk, vv, pos, pos, impl=impl,
                            block_k=8).sum()
        return jax.grad(loss, argnums=(0, 1, 2))(q, k, v)

    for g1, g2 in zip(f("naive"), f("chunked")):
        np.testing.assert_allclose(np.asarray(g1), np.asarray(g2),
                                   rtol=2e-5, atol=2e-5)


def test_dense_forward_chunked_config():
    """End-to-end: a dense model with attn_impl=chunked matches naive."""
    from repro.models import transformer as T
    cfg_n = registry.get_config("tinyllama-1.1b").reduced()
    cfg_c = dataclasses.replace(cfg_n, attn_impl="chunked", attn_block_k=8)
    params = T.init_params(jax.random.PRNGKey(0), cfg_n)
    toks = jax.random.randint(jax.random.PRNGKey(1), (2, 32), 0, cfg_n.vocab)
    lg_n = T.forward(params, toks, cfg_n, RULES, remat=False)
    lg_c = T.forward(params, toks, cfg_c, RULES, remat=False)
    np.testing.assert_allclose(np.asarray(lg_n), np.asarray(lg_c),
                               rtol=3e-4, atol=3e-4)


def test_microbatch_grad_accumulation_matches_full_batch():
    """n_micro>1 averages to the same gradients (and loss) as one batch."""
    from repro.optim import adamw, coord
    cfg = registry.get_config("smollm-360m").reduced()
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    batch_specs = {k: jax.ShapeDtypeStruct(v.shape, v.dtype)
                   for k, v in registry.make_train_batch(
                       jax.random.PRNGKey(0), cfg, 8, 16).items()}
    opt = adamw.AdamWConfig(lr=1e-2, warmup_steps=1, total_steps=10,
                            clip_mode="none", weight_decay=0.0)
    outs = []
    for n_micro in (1, 4):
        cc = coord.CoordConfig(mode="sync", microbatch=n_micro)
        setup = coord.build(cfg, Rules(batch=("pod", "data")), mesh, cc, opt,
                            lambda c, r: registry.make_loss_fn(c, r, remat=False),
                            batch_specs)
        state = setup.init_fn(jax.random.PRNGKey(0))
        batch = registry.make_train_batch(jax.random.PRNGKey(1), cfg, 8, 16)
        state = setup.step_fn(state, batch)
        outs.append(state)
    w1 = jax.tree_util.tree_leaves(outs[0].params)
    w4 = jax.tree_util.tree_leaves(outs[1].params)
    for a, b in zip(w1, w4):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                                   rtol=2e-3, atol=2e-3)


_A2A_SUBPROC = r"""
import dataclasses
import jax, jax.numpy as jnp
from repro.configs import registry
from repro.models import moe
from repro.models.sharding import Rules
mesh = jax.make_mesh((1, 2, 4), ("pod", "data", "model"))
cfg0 = registry.get_config("olmoe-1b-7b").reduced()
cfg_ref = dataclasses.replace(cfg0, capacity_factor=16.0)
cfg_a2a = dataclasses.replace(cfg0, capacity_factor=16.0, moe_a2a=True)
params = registry.init_params(jax.random.PRNGKey(0), cfg_ref)
rules = Rules(batch=("pod", "data"))
x = jax.random.normal(jax.random.PRNGKey(2), (4, 16, cfg0.d_model))
lp = jax.tree.map(lambda p: p[0], params["layers"])
with jax.set_mesh(mesh):
    out_ref, st_ref = jax.jit(
        lambda p, xx: moe.moe_apply(p, xx, cfg_ref, rules))(lp["moe"], x)
    out_a2a, st_a2a = jax.jit(
        lambda p, xx: moe.moe_apply_a2a(p, xx, cfg_a2a, rules))(lp["moe"], x)
    err = float(jnp.abs(out_ref - out_a2a).max())
    assert err < 1e-5, err
    assert jnp.array_equal(st_ref.expert_load, st_a2a.expert_load)
    g = jax.jit(jax.grad(lambda p: moe.moe_apply_a2a(
        p, x, cfg_a2a, rules)[0].sum()))(lp["moe"])
    assert all(bool(jnp.isfinite(l).all())
               for l in jax.tree_util.tree_leaves(g))
print("A2A-OK")
"""


@pytest.mark.slow
def test_alltoall_ep_matches_reference_subprocess():
    """Explicit all-to-all EP == auto-SPMD reference on a 1x2x4 mesh
    (8 simulated devices kept out of this process)."""
    import os
    import subprocess
    import sys

    env = dict(os.environ)
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    env["PYTHONPATH"] = os.path.abspath(
        os.path.join(os.path.dirname(__file__), "..", "src"))
    out = subprocess.run([sys.executable, "-c", _A2A_SUBPROC], env=env,
                         capture_output=True, text=True, timeout=560)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "A2A-OK" in out.stdout


def test_a2a_falls_back_without_expert_axis():
    """On a 1-wide expert axis the a2a path must defer to blocked/global."""
    cfg = dataclasses.replace(registry.get_config("olmoe-1b-7b").reduced(),
                              moe_a2a=True, capacity_factor=16.0)
    params = registry.init_params(jax.random.PRNGKey(0), cfg)
    lp = jax.tree.map(lambda p: p[0], params["layers"])
    x = jax.random.normal(jax.random.PRNGKey(2), (2, 8, cfg.d_model))
    mesh = jax.make_mesh((1, 1, 1), ("pod", "data", "model"))
    with jax.set_mesh(mesh):
        out, stats = moe.moe_apply_a2a(lp["moe"], x, cfg,
                                       Rules(batch=("pod", "data")))
    ref, _ = moe.moe_apply(lp["moe"], x, cfg, Rules.disabled())
    np.testing.assert_allclose(np.asarray(out), np.asarray(ref),
                               rtol=2e-4, atol=2e-4)
