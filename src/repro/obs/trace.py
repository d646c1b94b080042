"""Spans and named phases of the executor, on the profiler's clock.

Two kinds of mark, both inert unless a JAX profiler session is active:

* **host spans** — :func:`span` is a ``jax.profiler.TraceAnnotation``: a
  TraceMe event on the profiler's host plane, nested on the calling thread,
  on the same clock as the device planes. ``FusedExecutor.run`` /
  ``run_escrow`` mark every phase of a call with the ``EXEC_*`` names, so a
  trace says what the host did in each stretch of device idleness.
* **device phases** — :func:`phase` is a ``jax.named_scope``: it puts its
  name into the HLO metadata (``op_name="jit(f)/.../txn.neworder/..."``) of
  every operation traced under it and changes nothing else in the program.

A device trace names an operation by its HLO instruction alone, so the
phase of an operation is read from the compiled program's text. At warm-up
the executor :func:`register` s each jitted program it dispatches with its
argument shapes and shardings (no compile); :func:`scope_table` lowers and
compiles them only when a reader asks — the compile is the one already in
JAX's cache — and maps each instruction to its phase.
"""

from __future__ import annotations

import functools
import re

import jax
import numpy as np

# host spans (TraceMe names)
EXEC_CALL = "exec.call"            # one FusedExecutor.run / run_escrow call
# a call's opening: the state leaves not yet on the run sharding are put
# there, the ring and counters come from one program (the counts of both
# are the span's metadata), then the retry ring and metrics lattice if used
EXEC_PREPARE = "exec.prepare"
EXEC_MEGASTEP = "exec.megastep"    # one megastep dispatch with its upload
EXEC_DRAIN = "exec.drain"          # one drain dispatch
EXEC_REFRESH = "exec.refresh"      # one drain + share refresh dispatch
EXEC_SYNC = "exec.sync"            # the final block_until_ready
EXEC_READBACK = "exec.readback"    # host reads of rejects and the retry ring
EXEC_OBS_FOLD = "exec.obs_fold"    # deferred metrics-lattice folds
AUDIT = "audit"                    # drivers.run_loop's end-of-run audit

# device phases (named scopes)
TXN_NEWORDER = "txn.neworder"      # admission, effects, the ring write
TXN_PAYMENT = "txn.payment"
TXN_READS = "txn.reads"            # RAMP Order-Status and Stock-Level
TXN_DELIVERY = "txn.delivery"
DRAIN_APPLY = "drain.apply"        # outbox drain (merge, strict, retry)
DRAIN_REFRESH = "drain.refresh"    # escrow share refresh
PHASES = (TXN_NEWORDER, TXN_PAYMENT, TXN_READS, TXN_DELIVERY, DRAIN_APPLY,
          DRAIN_REFRESH)
UNSCOPED = "unscoped"              # loop overhead, compiler-inserted copies


def span(name: str):
    """Host span ``name`` around a ``with`` block."""
    return jax.profiler.TraceAnnotation(name)


def spanned(name: str):
    """Decorator: every call of the function is host span ``name``."""
    def wrap(fn):
        @functools.wraps(fn)
        def call(*args, **kwargs):
            with span(name):
                return fn(*args, **kwargs)
        return call
    return wrap


def phase(name: str):
    """Device phase ``name`` around the operations traced in a block."""
    return jax.named_scope(name)


def _arg_spec(x):
    if isinstance(x, jax.Array):
        # an uncommitted array (jnp.asarray of a host value) follows the
        # program's placement at dispatch; a committed one keeps its own
        committed = getattr(x, "_committed", True)
        return jax.ShapeDtypeStruct(
            x.shape, x.dtype, weak_type=x.aval.weak_type,
            sharding=x.sharding if committed else None)
    x = np.asarray(x)
    return jax.ShapeDtypeStruct(x.shape, x.dtype)


# The programs dispatched in this process, one entry per program name (the
# latest argument specs), and their scope table once a reader asked: the
# benchmark's metric readers find it here after the run, with no handle on
# the executor that dispatched.
_PROGRAMS: dict[str, tuple] = {}
_TABLE: dict[str, dict[str, str]] | None = None


def register(fn, *args) -> None:
    """Note jitted program ``fn`` as dispatched with ``args``; keeps their
    shapes, dtypes and shardings, never the arrays, and compiles nothing."""
    global _TABLE
    _PROGRAMS[fn.__name__] = (fn, jax.tree.map(_arg_spec, args))
    _TABLE = None


def scope_table() -> dict[str, dict[str, str]]:
    """``{module name: {instruction name: phase}}`` over the registered
    programs. The phase is the first of :data:`PHASES` in the instruction's
    ``op_name``, else :data:`UNSCOPED`. Computed on the first call after a
    registration."""
    global _TABLE
    if _TABLE is None:
        _TABLE = {}
        for fn, specs in _PROGRAMS.values():
            module, scopes = program_scopes(fn.lower(*specs))
            if scopes:
                _TABLE[module] = scopes
    return _TABLE


def _reset() -> None:
    """Forget every registered program (for tests)."""
    global _TABLE
    _PROGRAMS.clear()
    _TABLE = None


_MODULE = re.compile(r"^HloModule ([^\s,]+)")
_INSTR = re.compile(r"^\s*(?:ROOT )?%?([^\s=]+) = ")
_SIGNATURE = re.compile(r" = (.*?) ([\w-]+)\(")
_OP_NAME = re.compile(r'metadata=\{[^}]*?op_name="([^"]*)"')


def phase_of(op_name: str) -> str:
    for part in op_name.split("/"):
        if part in PHASES:
            return part
    return UNSCOPED


def _instructions(text: str) -> list[tuple[str, tuple, str]]:
    """``(name, (shape, opcode), phase)`` of each instruction of an HLO
    text, in the order printed (the schedule's, for a compiled program)."""
    out = []
    for line in text.splitlines():
        i = _INSTR.match(line)
        if i:
            sig = _SIGNATURE.search(line)
            o = _OP_NAME.search(line)
            out.append((i[1], sig.groups() if sig else (),
                        phase_of(o[1]) if o else UNSCOPED))
    return out


def hlo_scopes(text: str) -> tuple[str, dict[str, str]]:
    """Module name and ``{instruction: phase}`` of a compiled HLO text."""
    m = _MODULE.match(text)
    return (m[1] if m else "",
            {name: phase for name, _, phase in _instructions(text)})


def carry_phases(ran: str, own: str) -> dict[str, str]:
    """``{instruction of ran: phase}`` with the phases of ``own``, a compile
    of the same program that kept its metadata, position by position; empty
    unless every position holds the same opcode and shape in both."""
    ran_i, own_i = _instructions(ran), _instructions(own)
    if [sig for _, sig, _ in ran_i] != [sig for _, sig, _ in own_i]:
        return {}
    return {name: phase for (name, _, _), (_, _, phase) in zip(ran_i, own_i)}


def program_scopes(lowered) -> tuple[str, dict[str, str]]:
    """The scope map of the executable that runs a lowered program.

    The persistent compilation cache keys programs with their metadata
    stripped, so an executable compiled by a build without these scopes can
    stand in for this one, with the same schedule but instructions numbered
    apart. Then this build's program is compiled afresh (under a key that
    build never wrote: the option only embeds the HLO in the executable)
    and its phases carried over by :func:`carry_phases`."""
    text = lowered.compile().as_text()
    module, scopes = hlo_scopes(text)
    if set(scopes.values()) <= {UNSCOPED} and any(
            p in lowered.as_text(debug_info=True) for p in PHASES):
        own = lowered.compile({"xla_embed_ir_in_executable": True}).as_text()
        scopes = carry_phases(text, own)
    return module, scopes
