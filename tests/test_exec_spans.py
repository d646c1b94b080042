"""The executor's host spans and device phases (repro/obs/trace.py).

* a CPU profiler trace of one ``run`` / ``run_escrow`` call holds the
  ``exec.*`` spans, in order, nested under one ``exec.call``;
* the scope table maps instructions of both megasteps to each transaction
  phase and of the fused drain to ``drain.apply`` and ``drain.refresh``;
* registering programs at warm-up compiles nothing, and neither does the
  scope table of programs already dispatched;
* a call on the previous call's output compiles nothing and its
  ``exec.prepare`` moves no state leaf and dispatches one buffer program,
  by the executor's count and by the span's metadata; a call on host state
  places every leaf;
* an executable that the persistent cache serves from a build without the
  scopes (the cache key strips metadata) does not blank the table, and
  phases are carried over to it only where every position matches;
* the benchmark's readers use the same span and phase names.
"""

import jax
import numpy as np
import pytest

from bench import spans as bench_spans
from bench.trace import load_xplane
from repro.obs import trace
from repro.txn.engine import generate_mix_batches, single_host_engine
from repro.txn.executor import FusedExecutor, Prepared, stack_chunks
from repro.txn.tpcc import TPCCScale, TPCCState, init_state

SCALE = TPCCScale(n_warehouses=4, districts=4, customers=8, n_items=64,
                  order_capacity=128, max_lines=15)
N_CHUNKS = 2


class Compiles:
    """Backend compilations while armed (listeners cannot be removed)."""

    def __init__(self):
        self.armed, self.n = False, 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **kw):
        if self.armed and \
                event == "/jax/core/compile/backend_compile_duration":
            self.n += 1

    def count(self, fn):
        """Compilations during ``fn()``; its result is ``self.result``."""
        self.n, self.armed = 0, True
        try:
            self.result = fn()
        finally:
            self.armed = False
        return self.n


@pytest.fixture(scope="module")
def compiles():
    return Compiles()


def _host_state(stock_invariant):
    state = init_state(SCALE)
    if stock_invariant == "strict":
        state = state._replace(s_quantity=state.s_quantity * 20)
    return state


def _setup(stock_invariant):
    eng = single_host_engine(SCALE, stock_invariant=stock_invariant)
    ex = FusedExecutor(eng, ring_rows=4)
    chunks = stack_chunks(*generate_mix_batches(
        eng, batch_per_shard=8, n_batches=4 * N_CHUNKS, seed=5), 4)
    return eng, ex, chunks, eng.shard_state(_host_state(stock_invariant))


@pytest.fixture(scope="module")
def merge():
    return _setup("restock")


@pytest.fixture(scope="module")
def escrow():
    return _setup("strict")


def _run_merge(merge, warmup=True):
    _, ex, chunks, state = merge
    out = ex.run(jax.tree.map(lambda x: x.copy(), state), chunks,
                 warmup=warmup)
    jax.block_until_ready(out[0])


def _run_escrow(escrow, warmup=True):
    eng, ex, chunks, state = escrow
    state = jax.tree.map(lambda x: x.copy(), state)
    out = ex.run_escrow(state, eng.init_escrow(state), chunks,
                        warmup=warmup)
    jax.block_until_ready(out[0])


def _exec_spans(tmp_path, fn):
    """The ``exec.*`` host events of a profiler trace of ``fn()``."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        fn()
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    return sorted((e for e in load_xplane(path)
                   if e.name.startswith("exec.")),
                  key=lambda e: (e.start, -e.dur))


def _assert_nested_in_order(events, expected):
    call, *inner = events
    assert call.name == trace.EXEC_CALL
    assert [e.name for e in inner] == expected
    for e in inner:
        assert (e.plane, e.line) == (call.plane, call.line)
        assert call.start <= e.start and e.end <= call.end
    for a, b in zip(inner, inner[1:]):
        assert a.end <= b.start


def test_run_spans_nest_under_call(tmp_path, merge):
    events = _exec_spans(tmp_path, lambda: _run_merge(merge))
    _assert_nested_in_order(
        events, [trace.EXEC_PREPARE]
        + [trace.EXEC_MEGASTEP, trace.EXEC_DRAIN] * N_CHUNKS
        + [trace.EXEC_SYNC])


def test_run_escrow_spans_nest_under_call(tmp_path, escrow):
    events = _exec_spans(tmp_path, lambda: _run_escrow(escrow))
    _assert_nested_in_order(
        events, [trace.EXEC_PREPARE]
        + [trace.EXEC_MEGASTEP, trace.EXEC_REFRESH] * N_CHUNKS
        + [trace.EXEC_SYNC, trace.EXEC_READBACK])


def test_scope_table_maps_every_phase(merge, escrow, compiles):
    _run_merge(merge)
    _run_escrow(escrow)
    assert compiles.count(trace.scope_table) == 0, \
        "the dispatched programs compiled again"
    table = compiles.result
    txn = {trace.TXN_NEWORDER, trace.TXN_PAYMENT, trace.TXN_READS,
           trace.TXN_DELIVERY}
    for module in ("jit__megastep", "jit__megastep_escrow"):
        assert txn <= set(table[module].values()), module
    assert {trace.DRAIN_APPLY, trace.DRAIN_REFRESH} <= set(
        table["jit__drain_refresh"].values())
    assert trace.DRAIN_APPLY in table["jit__drain"].values()
    assert trace.UNSCOPED in table["jit__megastep"].values()


def test_registration_compiles_nothing(escrow, compiles):
    # a program never dispatched: registering it keeps shapes only
    fn = jax.jit(lambda x, y: x * y)
    x, y = np.ones((3,), np.float32), jax.device_put(np.ones((3,)))
    trace._reset()
    assert compiles.count(lambda: trace.register(fn, x, y)) == 0
    trace._reset()
    # a warm-up of programs already compiled registers them all, and
    # compiles nothing
    _run_escrow(escrow)
    assert compiles.count(lambda: _run_escrow(escrow)) == 0
    assert {"jit__megastep_escrow", "jit__drain_refresh",
            "jit__drain_strict"} <= set(trace.scope_table())


@pytest.mark.parametrize("regime", ["merge", "escrow"])
def test_steady_call_moves_no_state(request, regime, compiles, tmp_path):
    eng, ex, chunks, _ = request.getfixturevalue(regime)
    if regime == "merge":
        def call(state, esc, warmup=False):
            return ex.run(state, chunks, warmup=warmup)[0], None
    else:
        def call(state, esc, warmup=False):
            return ex.run_escrow(state, esc, chunks, warmup=warmup)[:2]
    host = _host_state("strict" if regime == "escrow" else "restock")
    esc = eng.init_escrow(eng.shard_state(host)) if regime == "escrow" \
        else None
    state, esc = call(host, esc, warmup=True)
    assert ex.last_prepare == Prepared(len(TPCCState._fields), 1)

    assert compiles.count(lambda: call(state, esc)) == 0, \
        "a call on the previous call's output compiled"
    assert ex.last_prepare == Prepared(moved_leaves=0, buffer_programs=1)
    state, esc = compiles.result

    jax.profiler.start_trace(str(tmp_path))
    try:
        jax.block_until_ready(call(state, esc))
    finally:
        jax.profiler.stop_trace()
    (path,) = tmp_path.rglob("*.xplane.pb")
    stats = [dict(e.stats)
             for plane in jax.profiler.ProfileData.from_file(str(path)).planes
             for line in plane.lines for e in line.events
             if e.name == trace.EXEC_PREPARE]
    assert stats == [{"moved_leaves": 0, "buffer_programs": 1}]


def test_scopes_survive_a_cache_entry_without_them(tmp_path):
    from jax.experimental.compilation_cache import compilation_cache as cc
    opts = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs",
            "jax_persistent_cache_min_entry_size_bytes")
    saved = {k: getattr(jax.config, k) for k in opts}

    def build(scoped):
        def prog(x):
            if scoped:
                with trace.phase(trace.TXN_PAYMENT):
                    return jax.numpy.sin(x) * 3
            return jax.numpy.sin(x) * 3
        return jax.jit(prog)

    x = jax.numpy.ones(8)
    try:
        for k, v in zip(opts, (str(tmp_path), 0, 0)):
            jax.config.update(k, v)
        cc.reset_cache()
        build(False)(x)          # a build without the scope fills the cache
        lowered = build(True).lower(x)
        assert trace.TXN_PAYMENT not in lowered.compile().as_text()
        _, scopes = trace.program_scopes(lowered)
    finally:
        for k, v in saved.items():
            jax.config.update(k, v)
        cc.reset_cache()
    assert trace.TXN_PAYMENT in scopes.values()


def test_hlo_scopes_reads_op_name():
    text = "\n".join([
        "HloModule jit__megastep, is_scheduled=true",
        "ENTRY %main {",
        '  %fusion.3 = s32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(_megastep)/while/body/txn.neworder/scatter-add" '
        'source_file="x.py" source_line=3}',
        '  ROOT %copy.1 = s32[8]{0} copy(%fusion.3)',
        '  %add.2 = s32[] add(%a, %b), metadata={op_name='
        '"jit(_drain)/drain.apply/drain.refresh/add"}',
        "}"])
    module, scopes = trace.hlo_scopes(text)
    assert module == "jit__megastep"
    assert scopes == {"fusion.3": trace.TXN_NEWORDER,
                      "copy.1": trace.UNSCOPED,
                      "add.2": trace.DRAIN_APPLY}


def test_carry_phases_checks_every_position():
    own = "\n".join([
        "HloModule jit__megastep, is_scheduled=true",
        '  %fusion.7 = s32[8]{0} fusion(%p), kind=kLoop, metadata='
        '{op_name="jit(_megastep)/txn.payment/mul"}',
        '  ROOT %copy.8 = (s32[8]{0}, f32[]) tuple(%fusion.7, %c)'])
    ran = own.replace(".7", ".2").replace(".8", ".3").replace(
        ', metadata={op_name="jit(_megastep)/txn.payment/mul"}', "")
    assert trace.carry_phases(ran, own) == {
        "fusion.2": trace.TXN_PAYMENT, "copy.3": trace.UNSCOPED}
    # the same length, but another opcode or shape at a position: nothing
    assert trace.carry_phases(ran.replace("fusion(", "add("), own) == {}
    assert trace.carry_phases(ran.replace("s32[8]{0} f", "s32[9]{0} f"),
                              own) == {}


def test_bench_names_are_the_programs():
    for name in ("EXEC_CALL", "EXEC_PREPARE", "EXEC_MEGASTEP", "EXEC_DRAIN",
                 "EXEC_REFRESH", "EXEC_SYNC", "EXEC_READBACK",
                 "TXN_NEWORDER", "TXN_PAYMENT", "TXN_READS", "TXN_DELIVERY",
                 "DRAIN_APPLY", "DRAIN_REFRESH", "UNSCOPED"):
        assert getattr(bench_spans, name) == getattr(trace, name), name
    assert all(getattr(trace, n).startswith(bench_spans.EXEC_PREFIX)
               for n in dir(trace) if n.startswith("EXEC_"))
