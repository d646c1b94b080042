"""Coordination-avoiding data parallelism — the paper's technique as the
training loop's execution engine.

The coordination plan (core/planner.py) classifies training state; this
module realizes the three execution modes on the (pod, data, model) mesh:

  * ``sync`` — the coordinated baseline (the "serializable" analog): one
    global SPMD program, gradients all-reduced across pod x data every step.
  * ``hierarchical`` — replicas = pods (paper Fig. 1): parameters carry a
    leading pod dimension and diverge; each step syncs gradients only inside
    a pod (cheap ICI, inserted automatically by SPMD); the expensive
    cross-pod (DCN) merge is DEFERRED to every k-th step and runs as an
    explicit anti-entropy ``merge_fn`` — convergence may lag the hot path
    (Definition 3), optionally compressed (optim/compression.py).
  * ``local_sgd`` — same mechanics with a long merge period.

Structural verification: the hot-path step of the deferred modes must
contain **no collective whose replica group crosses a pod boundary**
(utils/hlo.cross_pod_collectives) — the Definition-5 proof at mesh scale.

Metric state is mesh-native G-counters: per-pod slots, summed only when
read (merge at log boundaries — the planner's merge_every=0 class).
"""

from __future__ import annotations

import dataclasses
import functools
from typing import Any, Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from repro.models.sharding import Rules, opt_state_pspecs, param_pspecs

from . import adamw, compression

PyTree = Any


@dataclasses.dataclass(frozen=True)
class CoordConfig:
    mode: str = "sync"            # sync | hierarchical | local_sgd
    merge_every: int = 8          # cadence of the deferred cross-pod merge
    compress: str = "none"        # none | bf16 | int8
    merge_opt_state: bool = True  # also average Adam moments at merge time
    pod_axis: str = "pod"
    microbatch: int = 1           # gradient-accumulation steps per update
                                  # (activation memory divides by this)

    @property
    def deferred(self) -> bool:
        return self.mode in ("hierarchical", "local_sgd")


class TrainState(NamedTuple):
    params: PyTree
    opt: adamw.AdamWState
    step: jax.Array        # [] int32, replicated (identical local increments)
    loss_slots: jax.Array  # [n_pods] f32 G-counter slots
    token_slots: jax.Array  # [n_pods] f32
    grad_norm_slots: jax.Array  # [n_pods] f32 (last local grad norm)


def _under_mesh(fn: Optional[Callable], mesh: Mesh) -> Optional[Callable]:
    """Run a jitted fn with ``mesh`` in context (with_sharding_constraint
    inside the models takes raw PartitionSpecs)."""
    if fn is None:
        return None

    @functools.wraps(fn)
    def wrapped(*args, **kwargs):
        with jax.set_mesh(mesh):
            return fn(*args, **kwargs)

    def lower(*args, **kwargs):
        with jax.set_mesh(mesh):
            return fn.lower(*args, **kwargs)

    wrapped.lower = lower
    return wrapped


@dataclasses.dataclass
class TrainSetup:
    step_fn: Callable
    merge_fn: Optional[Callable]
    init_fn: Callable
    state_shardings: Any
    batch_shardings: Any
    mesh: Mesh
    coord: CoordConfig
    abstract_state: Any = None  # eval_shape of the initial state

    def __post_init__(self):
        self.step_fn = _under_mesh(self.step_fn, self.mesh)
        self.merge_fn = _under_mesh(self.merge_fn, self.mesh)
        self.init_fn = _under_mesh(self.init_fn, self.mesh)

    def read_metrics(self, state: TrainState) -> dict:
        """G-counter reads: sum the per-pod slots (log-boundary merge)."""
        return {
            "step": int(state.step),
            "loss_mean": float(state.loss_slots.sum())
            / max(int(state.step), 1) / max(state.loss_slots.shape[0], 1),
            "tokens": float(state.token_slots.sum()),
            "grad_norm_last": float(state.grad_norm_slots.max()),
        }


def _n_pods(mesh: Mesh, coord: CoordConfig) -> int:
    return mesh.shape[coord.pod_axis] if coord.pod_axis in mesh.shape else 1


def build(model_cfg, rules: Rules, mesh: Mesh, coord: CoordConfig,
          opt_cfg: adamw.AdamWConfig, make_loss_fn: Callable,
          batch_specs: dict) -> TrainSetup:
    """Assemble jitted step/merge functions for the chosen mode.

    ``make_loss_fn(model_cfg, rules)`` -> loss(params, batch).
    ``batch_specs``: dict of ShapeDtypeStructs for one global batch.
    """
    n_pods = _n_pods(mesh, coord)
    opt_cfg = dataclasses.replace(opt_cfg, num_replicas=n_pods)

    batch_axes = tuple(a for a in (coord.pod_axis, "data") if a in mesh.shape)
    batch_sharding = jax.tree.map(
        lambda _: NamedSharding(mesh, P(batch_axes)), batch_specs)

    if not coord.deferred:
        loss_fn = make_loss_fn(model_cfg, rules)
        return _build_sync(model_cfg, rules, mesh, coord, opt_cfg, loss_fn,
                           batch_specs, batch_sharding)
    # inside the pod-manual region only auto axes may appear in constraints:
    # activations' batch dim is sharded over 'data' alone (pod is manual)
    inner_rules = dataclasses.replace(
        rules, batch=tuple(a for a in (rules.batch or ())
                           if a != coord.pod_axis) or None)
    loss_fn = make_loss_fn(model_cfg, inner_rules)
    return _build_deferred(model_cfg, rules, mesh, coord, opt_cfg, loss_fn,
                           batch_specs, batch_sharding, n_pods)


# ---------------------------------------------------------------------------
# sync (coordinated baseline)
# ---------------------------------------------------------------------------


def _token_count(batch: dict) -> jax.Array:
    t = batch["tokens"]
    return jnp.asarray(t.shape[0] * t.shape[1], jnp.float32)


def _build_sync(model_cfg, rules, mesh, coord, opt_cfg, loss_fn,
                batch_specs, batch_sharding) -> TrainSetup:
    from repro.configs import registry

    def init_fn(rng):
        params = registry.init_params(rng, model_cfg)
        return TrainState(params, adamw.init(params),
                          jnp.zeros((), jnp.int32), jnp.zeros((1,)),
                          jnp.zeros((1,)), jnp.zeros((1,)))

    n_micro = max(coord.microbatch, 1)

    def _grads(params, batch):
        if n_micro == 1:
            return jax.value_and_grad(loss_fn)(params, batch)
        # gradient accumulation: scan over microbatches, f32 accumulators
        micro = jax.tree.map(
            lambda x: x.reshape((n_micro, x.shape[0] // n_micro) + x.shape[1:]),
            batch)
        g0 = jax.tree.map(lambda p: jnp.zeros(p.shape, jnp.float32), params)

        def body(acc, mb):
            loss_acc, g_acc = acc
            loss, g = jax.value_and_grad(loss_fn)(params, mb)
            g_acc = jax.tree.map(lambda a, x: a + x.astype(jnp.float32),
                                 g_acc, g)
            return (loss_acc + loss, g_acc), None

        (loss_sum, grads), _ = jax.lax.scan(body, (jnp.zeros(()), g0), micro)
        grads = jax.tree.map(lambda g, p: (g / n_micro).astype(p.dtype),
                             grads, params)
        return loss_sum / n_micro, grads

    def step_fn(state: TrainState, batch: dict) -> TrainState:
        loss, grads = _grads(state.params, batch)
        params, opt, m = adamw.update(opt_cfg, grads, state.opt, state.params)
        return TrainState(
            params, opt, state.step + 1,
            state.loss_slots.at[0].add(loss),
            state.token_slots.at[0].add(_token_count(batch)),
            state.grad_norm_slots.at[0].set(m["grad_norm"]))

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))
    pspec = param_pspecs(abstract.params, rules)
    ospec = opt_state_pspecs(abstract.params, rules,
                             data_size=mesh.shape.get("data"))
    state_shardings = TrainState(
        jax.tree.map(lambda s: NamedSharding(mesh, s), pspec),
        adamw.AdamWState(
            jax.tree.map(lambda s: NamedSharding(mesh, s), ospec),
            jax.tree.map(lambda s: NamedSharding(mesh, s), ospec),
            NamedSharding(mesh, P())),
        NamedSharding(mesh, P()), NamedSharding(mesh, P()),
        NamedSharding(mesh, P()), NamedSharding(mesh, P()))

    jit_step = jax.jit(step_fn, in_shardings=(state_shardings, batch_sharding),
                       out_shardings=state_shardings, donate_argnums=0)
    jit_init = jax.jit(init_fn, out_shardings=state_shardings)
    return TrainSetup(jit_step, None, jit_init, state_shardings,
                      batch_sharding, mesh, coord, abstract)


# ---------------------------------------------------------------------------
# deferred (hierarchical / local_sgd): pod-replicated parameters
# ---------------------------------------------------------------------------


def _build_deferred(model_cfg, rules, mesh, coord, opt_cfg, loss_fn,
                    batch_specs, batch_sharding, n_pods) -> TrainSetup:
    from repro.configs import registry

    pod = coord.pod_axis

    def init_fn(rng):
        params = registry.init_params(rng, model_cfg)
        # one copy per pod (leading pod dim); identical at t=0
        params = jax.tree.map(
            lambda x: jnp.broadcast_to(x, (n_pods, *x.shape)), params)
        opt = adamw.init(params)  # moments carry the pod dim too
        opt = opt._replace(count=jnp.zeros((), jnp.int32))
        return TrainState(params, opt, jnp.zeros((), jnp.int32),
                          jnp.zeros((n_pods,)), jnp.zeros((n_pods,)),
                          jnp.zeros((n_pods,)))

    # -- hot path: pod-manual shard_map, data/model stay automatic ----------
    def step_local(state: TrainState, batch: dict) -> TrainState:
        params = jax.tree.map(lambda x: x[0], state.params)
        opt = adamw.AdamWState(jax.tree.map(lambda x: x[0], state.opt.mu),
                               jax.tree.map(lambda x: x[0], state.opt.nu),
                               state.opt.count)
        loss, grads = jax.value_and_grad(loss_fn)(params, batch)
        params, opt, m = adamw.update(opt_cfg, grads, opt, params)
        lead = lambda t: jax.tree.map(lambda x: x[None], t)
        return TrainState(
            lead(params),
            adamw.AdamWState(lead(opt.mu), lead(opt.nu), opt.count),
            state.step + 1,
            state.loss_slots + loss[None],
            state.token_slots + _token_count(batch)[None],
            jnp.broadcast_to(m["grad_norm"], state.grad_norm_slots.shape))

    # -- anti-entropy: explicit cross-pod merge ------------------------------
    def merge_local(state: TrainState) -> TrainState:
        params = compression.merge_mean(state.params, pod, n_pods,
                                        coord.compress)
        opt = state.opt
        if coord.merge_opt_state:
            opt = adamw.AdamWState(
                compression.merge_mean(opt.mu, pod, n_pods, coord.compress),
                compression.merge_mean(opt.nu, pod, n_pods, coord.compress),
                opt.count)
        return state._replace(params=params, opt=opt)

    abstract = jax.eval_shape(init_fn, jax.random.PRNGKey(0))

    def pod_spec_tree(tree, inner_rules_fn):
        inner = inner_rules_fn(jax.tree.map(lambda s:
                                            jax.ShapeDtypeStruct(s.shape[1:],
                                                                 s.dtype),
                                            tree), rules)
        return jax.tree.map(lambda s: P(pod, *tuple(s)), inner)

    # full specs (pod + inner TP/ZeRO layout) drive the outer jit shardings;
    # shard_map is manual over 'pod' ONLY, so its specs mention just 'pod'
    params_spec = pod_spec_tree(abstract.params, param_pspecs)
    mu_spec = pod_spec_tree(abstract.opt.mu,
                            lambda t, r: opt_state_pspecs(
                                t, r, data_size=mesh.shape.get("data")))
    state_specs = TrainState(
        params_spec,
        adamw.AdamWState(mu_spec, mu_spec, P()),
        P(), P(pod), P(pod), P(pod))

    manual_specs = TrainState(
        jax.tree.map(lambda _: P(pod), abstract.params),
        adamw.AdamWState(jax.tree.map(lambda _: P(pod), abstract.opt.mu),
                         jax.tree.map(lambda _: P(pod), abstract.opt.nu),
                         P()),
        P(), P(pod), P(pod), P(pod))
    batch_pod_specs = jax.tree.map(lambda _: P(pod), batch_specs)

    sm_step = jax.shard_map(step_local, mesh=mesh,
                            in_specs=(manual_specs, batch_pod_specs),
                            out_specs=manual_specs,
                            axis_names={pod}, check_vma=False)
    sm_merge = jax.shard_map(merge_local, mesh=mesh,
                             in_specs=(manual_specs,),
                             out_specs=manual_specs,
                             axis_names={pod}, check_vma=False)

    state_shardings = jax.tree.map(
        lambda s: NamedSharding(mesh, s), state_specs,
        is_leaf=lambda x: isinstance(x, P))
    jit_step = jax.jit(sm_step, in_shardings=(state_shardings, batch_sharding),
                       out_shardings=state_shardings, donate_argnums=0)
    jit_merge = jax.jit(sm_merge, in_shardings=(state_shardings,),
                        out_shardings=state_shardings, donate_argnums=0)
    jit_init = jax.jit(init_fn, out_shardings=state_shardings)
    return TrainSetup(jit_step, jit_merge, jit_init, state_shardings,
                      batch_sharding, mesh, coord, abstract)
