import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (architecture x input shape) on
the production meshes, record memory/cost/collective analyses.

This is the scale proof the CPU container can give: for each of the 40
(arch x shape) cells, ``jax.jit(step).lower(**specs).compile()`` must succeed
on the 16x16 single-pod mesh AND the 2x16x16 multi-pod mesh — sharding
mismatches, compile-time OOMs, or unsupported collectives are bugs. The
compiled artifacts feed EXPERIMENTS.md:

  * memory_analysis()  -> bytes per device (does it fit 16 GB HBM?)
  * cost_analysis()    -> HLO FLOPs / bytes for the roofline terms
  * compiled.as_text() -> collective inventory + bytes (utils/hlo.py)

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch all --shape all \
      --mesh both --out results/dryrun.json
  PYTHONPATH=src python -m repro.launch.dryrun --arch tpcc --mesh both
"""

import argparse
import dataclasses
import json
import time
import traceback

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs import registry
from repro.kernels.escrow_admit import LANES, lane_rows
from repro.models.config import SHAPES, ModelConfig, ShapeConfig
from repro.models.sharding import Rules, param_pspecs
from repro.optim import adamw, coord
from repro.utils.hlo import collective_stats, cross_pod_collectives

from .mesh import make_production_mesh


def _rules(mesh, layout: str = "tp") -> Rules:
    batch = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if layout == "sp":
        # sequence parallelism, no tensor parallelism: activations shard the
        # sequence over the model axis; weights replicate (small models)
        return Rules(batch=batch, seq="model", model=None, expert=None,
                     layer_opt="data")
    return Rules(batch=batch, model="model", expert="model", layer_opt="data")


def _shape_divisible(n: int, mesh, axes: tuple) -> bool:
    size = 1
    for a in axes:
        size *= mesh.shape.get(a, 1)
    return n % size == 0


def lower_train(arch: str, cfg: ModelConfig, shape: ShapeConfig, mesh,
                coord_mode: str = "sync", merge_every: int = 8,
                compress: str = "none", remat: bool = True,
                microbatch: int = 1):
    rules = _rules(mesh)
    batch_specs = registry.train_input_specs(cfg, shape)
    cc = coord.CoordConfig(mode=coord_mode, merge_every=merge_every,
                           compress=compress, microbatch=microbatch)
    setup = coord.build(
        cfg, rules, mesh, cc,
        adamw.AdamWConfig(clip_mode="escrow"),
        lambda c, r: registry.make_loss_fn(c, r, use_flash=False, remat=remat),
        batch_specs)
    lowered = setup.step_fn.lower(setup.abstract_state, batch_specs)
    merged_lowered = (setup.merge_fn.lower(setup.abstract_state)
                      if setup.merge_fn is not None else None)
    return lowered, merged_lowered


def _serving_params_abs(cfg: ModelConfig):
    """Serving lowers weights in the compute dtype (bf16), not f32 masters."""
    dt = jnp.dtype(cfg.dtype)

    def cast(l):
        if jnp.issubdtype(l.dtype, jnp.floating):
            return jax.ShapeDtypeStruct(l.shape, dt)
        return l
    return jax.tree.map(cast, registry.abstract_params(cfg))


def lower_prefill(arch: str, cfg: ModelConfig, shape: ShapeConfig, mesh,
                  layout: str = "tp"):
    rules = _rules(mesh, layout)
    batch_specs = registry.train_input_specs(cfg, shape)
    batch_specs.pop("labels")
    prefill = registry.make_prefill_fn(cfg, rules)
    params_abs = _serving_params_abs(cfg)
    pspecs = param_pspecs(params_abs, rules)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    batch_sh = jax.tree.map(lambda _: NamedSharding(mesh, P(batch_axes)),
                            batch_specs)
    with jax.set_mesh(mesh):
        return jax.jit(prefill, in_shardings=(param_sh, batch_sh)).lower(
            params_abs, batch_specs), None


def _cache_shardings(cfg: ModelConfig, cache_specs, mesh, batch: int):
    """Shard caches: batch over (pod, data) when divisible; KV/head-like dims
    over model when divisible; else replicate that dim."""
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    model_size = mesh.shape.get("model", 1)
    batch_ok = _shape_divisible(batch, mesh, batch_axes)

    def spec_for(leaf):
        nd = len(leaf.shape)
        if nd == 0:
            return P()
        spec = [None] * nd
        # find the batch dim (== batch) and a model-shardable dim
        for i, d in enumerate(leaf.shape):
            if d == batch and batch_ok and spec[i] is None and batch_axes:
                spec[i] = batch_axes
                break
        for i in range(nd - 1, -1, -1):
            if spec[i] is None and leaf.shape[i] % model_size == 0 \
                    and leaf.shape[i] >= model_size and i >= 2:
                spec[i] = "model"
                break
        return P(*spec)

    return jax.tree.map(lambda l: NamedSharding(mesh, spec_for(l)),
                        cache_specs)


def lower_decode(arch: str, cfg: ModelConfig, shape: ShapeConfig, mesh):
    rules = _rules(mesh)
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.shape)
    if not _shape_divisible(shape.global_batch, mesh, batch_axes):
        # long_500k (batch=1): model parallelism only, batch replicated
        rules = dataclasses.replace(rules, batch=None)
    decode = registry.make_decode_fn(cfg, rules)
    params_abs = _serving_params_abs(cfg)
    cache_specs, token_spec = registry.decode_input_specs(cfg, shape)

    pspecs = param_pspecs(params_abs, rules)
    param_sh = jax.tree.map(lambda s: NamedSharding(mesh, s), pspecs,
                            is_leaf=lambda x: isinstance(x, P))
    cache_sh = _cache_shardings(cfg, cache_specs, mesh, shape.global_batch)
    token_sh = NamedSharding(
        mesh, P(batch_axes) if _shape_divisible(shape.global_batch, mesh,
                                                batch_axes) else P())
    with jax.set_mesh(mesh):
        return jax.jit(decode, in_shardings=(param_sh, cache_sh, token_sh)
                       ).lower(params_abs, cache_specs, token_spec), None


def lower_tpcc(mesh, batch_per_shard: int = 16, chunk_len: int = 4):
    """The paper's own workload at spec cardinalities.

    Returns (lowered New-Order hot path, {name: lowered RAMP read path},
    lowered fused megastep, lowered escrow hot path, escrow engine) — the
    coordination-freedom claims: writes avoid coordination (Definition 5),
    reads stay atomic without it (RAMP, txn/ramp.py), the fused full-mix
    scan (txn/executor.py) keeps both properties for ``chunk_len`` whole
    iterations per dispatch, and the plan-selected ESCROW regime's strict-
    stock New-Order (txn/tpcc.py apply_neworder_escrow) is collective-free
    between share refreshes even at spec scale.
    """
    from repro.configs.tpcc import config as tpcc_config
    from repro.txn.engine import Engine
    from repro.txn.executor import FusedExecutor

    axes = tuple(a for a in ("pod", "data", "model") if a in mesh.shape)
    n_shards = 1
    for a in axes:
        n_shards *= mesh.shape[a]
    scale = tpcc_config(n_warehouses=2 * n_shards)
    eng = Engine(scale, mesh, axes)
    reads = {
        "order_status": eng.lowered_order_status(batch_per_shard),
        "stock_level": eng.lowered_stock_level(batch_per_shard),
    }
    megastep = FusedExecutor(eng, ring_rows=chunk_len).lowered_megastep(
        chunk_len=chunk_len, batch_per_shard=batch_per_shard,
        read_per_shard=max(1, batch_per_shard // 4))
    eng_escrow = Engine(scale, mesh, axes, stock_invariant="strict")
    escrow = eng_escrow.lowered_neworder_escrow(batch_per_shard)
    # the fused escrow megastep (sparse hot-set carry in the donated scan):
    # chunk_len strict-stock mix iterations between refreshes, at spec scale
    escrow_megastep = FusedExecutor(
        eng_escrow, ring_rows=chunk_len).lowered_megastep(
        chunk_len=chunk_len, batch_per_shard=batch_per_shard,
        read_per_shard=max(1, batch_per_shard // 4))
    # two-level admission at spec scale: admission="kernel" forces the
    # contention gate + residual FCFS pipeline into the escrow hot path
    # (off-TPU the Level-2 lowering is the jitted fori_loop fallback; on TPU
    # it is the Pallas kernel with avail in VMEM scratch)
    eng_admit = Engine(scale, mesh, axes, stock_invariant="strict",
                       admission="kernel")
    admission = eng_admit.lowered_neworder_escrow(batch_per_shard)
    # the ONE-KERNEL megastep (effects="fused"): admission + committed
    # effects + RAMP stamps over one VMEM residency of the hot tiles
    # (kernels/txn_megastep.py), lowered at spec scale
    eng_fused = Engine(scale, mesh, axes, stock_invariant="strict",
                       admission="kernel", effects="fused")
    fused_effects = eng_fused.lowered_neworder_escrow(batch_per_shard)
    return (eng.lowered_neworder(batch_per_shard), reads, megastep, escrow,
            escrow_megastep, eng_escrow, admission, eng_admit,
            fused_effects, eng_fused, batch_per_shard)


_ESCROW_AUDIT_MEMO: dict = {}


def tpcc_escrow_audit_cell() -> dict:
    """A small CONCRETE escrow run + consistency audit inside the dry-run:
    tier-1 scale on one of this process's devices, strict stock + escrow
    conservation checked by the independent oracle (txn/audit.py).

    Memoized: the run is mesh-independent (it always builds its own
    1-device mesh), so a multi-mesh sweep pays the compile+run cost once.
    """
    if _ESCROW_AUDIT_MEMO:
        return dict(_ESCROW_AUDIT_MEMO)
    from jax.sharding import Mesh

    from repro.txn.audit import audit_tpcc
    from repro.txn.engine import Engine, run_escrow_loop
    from repro.txn.tpcc import TPCCScale, init_state

    mesh = Mesh(np.array(jax.devices()[:1]), ("data",))
    scale = TPCCScale(n_warehouses=4, districts=4, customers=8, n_items=64,
                      order_capacity=128, max_lines=15)
    eng = Engine(scale, mesh, ("data",), stock_invariant="strict",
                 hot_items=8)
    state = eng.shard_state(init_state(scale))
    q0 = state.s_quantity.copy()
    state, esc, stats = run_escrow_loop(
        eng, state, batch_per_shard=8, n_batches=6, merge_every=2,
        refresh_every=2, seed=0, mix=False, fused=False,
        item_skew=1.1)
    rep = audit_tpcc(state, escrow=esc, initial_stock=q0, strict_stock=True)
    _ESCROW_AUDIT_MEMO.update(
        committed=stats.neworders, aborts=stats.aborts,
        refreshes=stats.refreshes, cold_rejects=stats.cold_rejects,
        escrow_layout=eng.escrow_layout, audit_ok=rep.ok,
        audit_failures=rep.failures)
    return dict(_ESCROW_AUDIT_MEMO)


# ---------------------------------------------------------------------------


def analyze(lowered, mesh, label: str, trip_counts=(),
            compile_seconds_budget: float = 1800,
            return_text: bool = False):
    t0 = time.perf_counter()
    compiled = lowered.compile()
    compile_s = time.perf_counter() - t0

    out = {"label": label, "compile_seconds": round(compile_s, 2)}
    try:
        mem = compiled.memory_analysis()
        out["memory"] = {
            "argument_bytes": getattr(mem, "argument_size_in_bytes", None),
            "output_bytes": getattr(mem, "output_size_in_bytes", None),
            "temp_bytes": getattr(mem, "temp_size_in_bytes", None),
            "generated_code_bytes": getattr(
                mem, "generated_code_size_in_bytes", None),
        }
    except Exception as e:  # pragma: no cover
        out["memory"] = {"error": str(e)}
    try:
        cost = compiled.cost_analysis()
        out["cost"] = {k: cost.get(k) for k in
                       ("flops", "bytes accessed", "transcendentals",
                        "optimal_seconds") if k in cost}
    except Exception as e:  # pragma: no cover
        out["cost"] = {"error": str(e)}

    text = compiled.as_text()
    stats = collective_stats(text)
    from benchmarks.roofline import loop_scaled_collective_bytes
    out["collectives"] = {
        "counts": dict(stats.counts),
        "bytes": stats.total_bytes(),
        "loop_scaled_bytes": loop_scaled_collective_bytes(text, trip_counts),
        "describe": stats.describe(),
    }
    if "pod" in mesh.shape:
        pod_size = 1
        for a in mesh.shape:
            if a != "pod":
                pod_size *= mesh.shape[a]
        xp = cross_pod_collectives(text, pod_size)
        out["collectives"]["cross_pod"] = len(xp)
        _, xbytes = loop_scaled_collective_bytes(text, trip_counts, pod_size)
        out["collectives"]["cross_pod_scaled_bytes"] = xbytes
    if return_text:
        return out, text
    return out


def apply_overrides(cfg: ModelConfig, overrides: str) -> ModelConfig:
    """--set key=value[,key=value...] config overrides (perf iterations)."""
    if not overrides:
        return cfg
    kv = {}
    for pair in overrides.split(","):
        k, v = pair.split("=")
        if v.lower() in ("true", "false"):
            v = v.lower() == "true"
        else:
            try:
                v = int(v)
            except ValueError:
                try:
                    v = float(v)
                except ValueError:
                    pass
        kv[k] = v
    return dataclasses.replace(cfg, **kv)


def run_cell(arch: str, shape_name: str, mesh, mesh_label: str,
             coord_mode: str = "sync", remat: bool = True,
             overrides: str = "", merge_every: int = 8,
             compress: str = "none", microbatch: int = 1,
             layout: str = "tp") -> dict:
    cell = {"arch": arch, "shape": shape_name, "mesh": mesh_label,
            "coord_mode": coord_mode, "overrides": overrides,
            "layout": layout}
    if arch == "tpcc":
        try:
            (lowered, reads, megastep, escrow, escrow_megastep,
             eng_escrow, admission, eng_admit, fused_effects, eng_fused,
             bps) = lower_tpcc(mesh)
            cell.update(analyze(lowered, mesh, "tpcc-neworder", ()))
            # the RAMP read transactions must compile collective-free at
            # spec scale — the structural atomic-visibility-without-
            # coordination claim (txn/ramp.py)
            cell["ramp_reads"] = {}
            for name, rl in reads.items():
                r = analyze(rl, mesh, f"tpcc-{name}", ())
                cell["ramp_reads"][name] = r
                if r["collectives"]["counts"]:
                    raise AssertionError(
                        f"RAMP {name} read path has collectives at spec "
                        f"scale: {r['collectives']['describe']}")
            # the fused megastep (txn/executor.py): chunk_len full-mix
            # iterations in one scan must stay collective-free at spec scale
            m = analyze(megastep, mesh, "tpcc-fused-megastep", ())
            cell["fused_megastep"] = m
            if m["collectives"]["counts"]:
                raise AssertionError(
                    f"fused megastep has collectives at spec scale: "
                    f"{m['collectives']['describe']}")
            # the plan-selected ESCROW regime (strict s_quantity >= 0): the
            # hot path must stay collective-free at spec scale while the
            # share refresh — the regime's only collective — must gather
            esc = analyze(escrow, mesh, "tpcc-escrow-neworder", ())
            cell["escrow_neworder"] = esc
            if esc["collectives"]["counts"]:
                raise AssertionError(
                    f"escrow hot path has collectives at spec scale: "
                    f"{esc['collectives']['describe']}")
            if eng_escrow.count_refresh_collectives().total_ops == 0:
                raise AssertionError("escrow refresh must communicate")
            # the FUSED escrow megastep: chunk_len whole strict-stock mix
            # iterations (sparse hot-set carry in the donated scan) must be
            # collective-free between refreshes, at spec scale
            em = analyze(escrow_megastep, mesh, "tpcc-escrow-megastep", ())
            cell["escrow_megastep"] = em
            if em["collectives"]["counts"]:
                raise AssertionError(
                    f"fused escrow megastep has collectives at spec scale: "
                    f"{em['collectives']['describe']}")
            # the two-tier layout's memory claim, at spec cardinalities:
            # the sparse hot-set table must cut per-device escrow residency
            # >= 50x vs the dense [R, W, I] share layout (ROADMAP item)
            mem = eng_escrow.escrow_bytes_per_device()
            cell["escrow_layout"] = mem
            if mem["layout"] != "sparse":
                raise AssertionError("spec-scale escrow engine must lower "
                                     "the sparse hot-set layout")
            if mem["reduction_vs_dense"] < 50:
                raise AssertionError(
                    f"sparse escrow layout cuts only "
                    f"{mem['reduction_vs_dense']:.1f}x vs dense "
                    f"(target >= 50x): {mem}")
            # TWO-LEVEL ADMISSION at spec scale: the contention-gated
            # escrow hot path (admission="kernel") must also compile
            # collective-free, and the availability vector the Pallas FCFS
            # kernel keeps resident in VMEM ([rows, 128] int32) must fit a
            # TPU core's 16 MiB default scoped VMEM (arithmetic here; Mosaic
            # itself checks it in tests/test_tpu_compile.py)
            adm = analyze(admission, mesh, "tpcc-escrow-admission", ())
            cell["escrow_admission"] = adm
            if adm["collectives"]["counts"]:
                raise AssertionError(
                    f"gate+kernel escrow admission has collectives at spec "
                    f"scale: {adm['collectives']['describe']}")
            A = (eng_admit.hot_keys.shape[0]
                 + eng_admit.w_per_shard * eng_admit.scale.n_items + 1)
            adm["avail_cells"] = A
            adm["avail_vmem_bytes"] = 4 * LANES * lane_rows(A)
            if adm["avail_vmem_bytes"] > 16 * 2 ** 20:
                raise AssertionError(
                    f"admission avail vector "
                    f"({adm['avail_vmem_bytes'] / 2**20:.1f} MB) "
                    f"exceeds the ~16 MB VMEM budget")
            # the ONE-KERNEL megastep (effects="fused") at spec scale: the
            # fused admission+effects+stamps hot path must also compile
            # collective-free, and the kernel's VMEM working set — avail +
            # the three stock slabs, each [rows, 128] int32 (the scalars
            # live in SMEM) — must fit a TPU core's 16 MiB default scoped
            # VMEM
            fm = analyze(fused_effects, mesh, "tpcc-megastep-fused", ())
            cell["megastep_fused"] = fm
            if fm["collectives"]["counts"]:
                raise AssertionError(
                    f"fused megastep effects path has collectives at spec "
                    f"scale: {fm['collectives']['describe']}")
            sc = eng_fused.scale
            Wl = eng_fused.w_per_shard
            Af = (eng_fused.hot_keys.shape[0] + Wl * sc.n_items + 1)
            vmem = 4 * LANES * (lane_rows(Af)
                                + 3 * lane_rows(Wl * sc.n_items))
            fm["megastep_vmem_bytes"] = vmem
            if vmem > 16 * 2 ** 20:
                raise AssertionError(
                    f"fused megastep working set ({vmem / 2**20:.1f} MB) "
                    f"exceeds the ~16 MB VMEM budget")
            # OBSERVABILITY PLANE at spec scale: the metrics-on escrow
            # megastep (the only regime where metrics change the program —
            # one stacked commit-mask output; the merge-regime program is
            # byte-identical, asserted in benchmarks obs_overhead) and the
            # deferred per-chunk record program must both compile
            # collective-free; their compiled HLO seeds a coordination
            # ledger whose hot budget is asserted at zero (the reuse path
            # CoordinationLedger.add documents for already-compiled text)
            from repro.obs.ledger import CoordinationLedger
            from repro.txn.executor import FusedExecutor as _FE
            ex_obs = _FE(eng_escrow, ring_rows=4)
            om, om_text = analyze(
                ex_obs.lowered_megastep(chunk_len=4, batch_per_shard=16,
                                        read_per_shard=4, metrics=True),
                mesh, "tpcc-escrow-megastep-metrics", (), return_text=True)
            orc, orc_text = analyze(ex_obs.lowered_record(4, 16), mesh,
                                    "tpcc-metrics-record", (),
                                    return_text=True)
            cell["obs_megastep_metrics"] = om
            cell["obs_record"] = orc
            led = CoordinationLedger(
                context=f"spec-scale escrow, metrics-on, mesh {mesh_label}")
            led.add("megastep (hot scan)", om_text, hot=True)
            led.add("metrics record", orc_text, hot=True)
            led.assert_budget()   # raises if the obs plane ever coordinates
            cell["obs_ledger"] = led.snapshot()
            # concrete tier-1-scale escrow run + consistency audit
            cell["escrow_audit"] = tpcc_escrow_audit_cell()
            if not cell["escrow_audit"]["audit_ok"]:
                raise AssertionError(
                    f"escrow audit failed: {cell['escrow_audit']}")
            cell["ok"] = True
        except Exception as e:
            cell.update(ok=False, error=f"{type(e).__name__}: {e}",
                        trace=traceback.format_exc()[-2000:])
        return cell

    cfg = apply_overrides(registry.get_config(arch), overrides)
    shape = SHAPES[shape_name]
    ok, why = registry.cell_supported(cfg, shape)
    if not ok:
        cell.update(ok=True, skipped=True, reason=why)
        return cell
    try:
        from benchmarks.roofline import trip_counts_for
        trips = trip_counts_for(cfg, shape)
        if shape.kind == "train" and microbatch > 1:
            trips = [microbatch] + trips  # grad-accumulation loop is level 0
        if shape.kind == "train":
            lowered, merge_lowered = lower_train(arch, cfg, shape, mesh,
                                                 coord_mode=coord_mode,
                                                 merge_every=merge_every,
                                                 compress=compress,
                                                 remat=remat,
                                                 microbatch=microbatch)
        elif shape.kind == "prefill":
            lowered, merge_lowered = lower_prefill(arch, cfg, shape, mesh,
                                                   layout=layout)
        else:
            lowered, merge_lowered = lower_decode(arch, cfg, shape, mesh)
        cell.update(analyze(lowered, mesh, f"{arch}/{shape_name}", trips))
        if merge_lowered is not None:
            cell["merge"] = analyze(merge_lowered, mesh, "merge", ())
        cell["ok"] = True
    except Exception as e:
        cell.update(ok=False, error=f"{type(e).__name__}: {e}",
                    trace=traceback.format_exc()[-2000:])
    return cell


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="all",
                    help="arch id, comma list, 'all', or 'tpcc'")
    ap.add_argument("--shape", default="all")
    ap.add_argument("--mesh", default="both", choices=["single", "multi", "both"])
    ap.add_argument("--coord", default="sync",
                    choices=["sync", "hierarchical", "local_sgd"])
    ap.add_argument("--no-remat", action="store_true")
    ap.add_argument("--set", dest="overrides", default="",
                    help="config overrides, e.g. attn_impl=chunked")
    ap.add_argument("--merge-every", type=int, default=8)
    ap.add_argument("--compress", default="none")
    ap.add_argument("--microbatch", type=int, default=1)
    ap.add_argument("--layout", default="tp", choices=["tp", "sp"],
                    help="prefill activation layout: tensor- or seq-parallel")
    ap.add_argument("--out", default="")
    args = ap.parse_args()

    archs = list(registry.ARCHS) if args.arch == "all" else args.arch.split(",")
    shapes = list(SHAPES) if args.shape == "all" else args.shape.split(",")
    meshes = {"single": [False], "multi": [True], "both": [False, True]}[args.mesh]

    results = []
    for multi in meshes:
        mesh = make_production_mesh(multi_pod=multi)
        label = "2x16x16" if multi else "16x16"
        for arch in archs:
            if arch == "tpcc":
                cell = run_cell("tpcc", "-", mesh, label)
                results.append(cell)
                print(json.dumps(cell)[:400], flush=True)
                continue
            for shape_name in shapes:
                cell = run_cell(arch, shape_name, mesh, label,
                                coord_mode=args.coord,
                                remat=not args.no_remat,
                                overrides=args.overrides,
                                merge_every=args.merge_every,
                                compress=args.compress,
                                microbatch=args.microbatch,
                                layout=args.layout)
                results.append(cell)
                print(json.dumps({k: v for k, v in cell.items()
                                  if k != "trace"})[:600], flush=True)

    n_fail = sum(1 for c in results if not c.get("ok"))
    print(f"\n{len(results)} cells, {n_fail} failures")
    if args.out:
        os.makedirs(os.path.dirname(args.out) or ".", exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(results, f, indent=1)
        print(f"wrote {args.out}")
    return 1 if n_fail else 0


if __name__ == "__main__":
    raise SystemExit(main())
