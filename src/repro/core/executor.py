"""Round-block execution engine: many rounds per device dispatch.

The per-round Python driver loop (seed ``run_cola`` / ``baselines._run``)
pays, every round, (a) a host->device dispatch of one jitted program and
(b) a blocking ``device_get`` sync whenever a metric is recorded. For the
paper's regime — cheap computation between communication rounds (Fig. 1) —
this framework overhead dominates wall-clock on fast hardware.

This module amortizes it: the round body runs inside a ``lax.scan`` over a
*block* of ``block_size`` rounds, so one dispatch executes the whole block.
Everything the host used to feed in per round (mixing matrices, active
masks, CD budgets, batches) is pre-materialized as stacked ``(T, ...)``
schedule arrays and sliced per block. The carried state is donated
(``donate_argnums``) so long runs reuse their ``(K, d)``/``(K, n_k)``
buffers instead of reallocating them every round.

Recording and run control are delegated to a pluggable ``Recorder``
(``repro.core.metrics``): its row is computed *on device* inside the scan
(a ``lax.cond`` on a per-round record flag, so skipped rounds cost
nothing) and fetched once at the end of the run. A recorder with a stop
condition (``stop_fn``, e.g. the Prop.-1 certificate's ``certified`` flag
or ``gap <= eps``) arms early exit: once a recorded row satisfies it, the
remaining rounds of the block turn into ``lax.cond`` no-ops (state passes
through bitwise-untouched) and the host skips all subsequent block
dispatches, at the price of one scalar stop-flag sync per block.

The engine is shared by all four drivers: the CoLA simulator
(``repro.core.cola.run_cola``), the decentralized baselines
(``repro.core.baselines``), the gossip-DP optimizer
(``repro.optim.gossip``) and the shard_map distributed runtime
(``repro.dist.runtime.run_dist_cola``).
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import hashlib
import types
import warnings
from collections import OrderedDict
from functools import partial
from typing import Any, Callable, NamedTuple

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax
from jax.extend import core as jcore

# Compiled-driver cache: jit only caches on the *function object*, and every
# run_cola/run_round_blocks call builds fresh closures, so without this each
# run re-traces and re-compiles its whole program — which dominates wall
# clock for short runs. Keys must be CONTENT-addressed (see ``fingerprint``):
# an id()-based key is wrong twice over — a rebuilt object at a recycled
# address silently reuses a driver whose closure baked in the OLD contents,
# and while an entry is live its closure pins the whole captured object.
# Bounded LRU.
_DRIVER_CACHE: OrderedDict = OrderedDict()
_DRIVER_CACHE_SIZE = 64


def _code_names(code: types.CodeType) -> set:
    """All global/attribute names a code object can reference, including
    from nested code (lambdas, comprehensions) — a global read inside a
    nested lambda bakes into the compiled driver just like a top-level one."""
    names = set(code.co_names)
    for c in code.co_consts:
        if isinstance(c, types.CodeType):
            names |= _code_names(c)
    return names


def _fp_update(h, obj, seen: set) -> None:
    """Feed ``obj``'s content (not its address) into the hash ``h``.

    Arrays hash by shape/dtype/bytes; functions hash by bytecode plus the
    contents of their closure cells and defaults — which is exactly the set
    of constants a jitted driver bakes into its executable (e.g. the label
    vector captured by ``Problem.grad_f``). ``seen`` guards cycles.
    """
    if isinstance(obj, (types.FunctionType, dict)) or (
            dataclasses.is_dataclass(obj) and not isinstance(obj, type)):
        if id(obj) in seen:
            h.update(b"<cycle>")
            return
        seen.add(id(obj))
    h.update(type(obj).__name__.encode())
    if obj is None or isinstance(obj, (bool, int, float, complex, str,
                                       bytes, np.generic)):
        h.update(repr(obj).encode())
    elif isinstance(obj, (np.ndarray, jax.Array)):
        arr = np.asarray(obj)
        h.update(str(arr.shape).encode())
        h.update(str(arr.dtype).encode())
        h.update(np.ascontiguousarray(arr).tobytes())
    elif isinstance(obj, jax.ShapeDtypeStruct):
        h.update(str(obj.shape).encode())
        h.update(str(obj.dtype).encode())
    elif isinstance(obj, (tuple, list)):
        for x in obj:
            _fp_update(h, x, seen)
    elif isinstance(obj, dict):
        for k in sorted(obj, key=repr):
            _fp_update(h, k, seen)
            _fp_update(h, obj[k], seen)
    elif isinstance(obj, functools.partial):
        _fp_update(h, obj.func, seen)
        _fp_update(h, obj.args, seen)
        _fp_update(h, dict(obj.keywords), seen)
    elif isinstance(obj, types.FunctionType):
        _fp_update(h, obj.__code__, seen)
        if obj.__closure__:
            for cell in obj.__closure__:
                try:
                    _fp_update(h, cell.cell_contents, seen)
                except ValueError:  # empty cell
                    h.update(b"<empty-cell>")
        _fp_update(h, obj.__defaults__, seen)
        _fp_update(h, obj.__kwdefaults__, seen)
        # module-level references: a function body that reads SCALE or calls
        # other_fn bakes their current values into the compiled driver, so
        # they are part of the content. Scalars/arrays hash by value; heavier
        # globals (modules, functions, classes) by qualified name — deep
        # enough to tell jnp.exp from jnp.log without walking module graphs.
        for name in sorted(_code_names(obj.__code__)):
            if name not in obj.__globals__:
                continue
            g = obj.__globals__[name]
            h.update(name.encode())
            if isinstance(g, types.ModuleType):
                h.update(g.__name__.encode())
            elif isinstance(g, (types.FunctionType, types.BuiltinFunctionType,
                                type)):
                h.update(f"{getattr(g, '__module__', '')}."
                         f"{getattr(g, '__qualname__', '')}".encode())
            elif g is None or isinstance(g, (bool, int, float, complex, str,
                                             bytes, np.generic, np.ndarray,
                                             jax.Array, tuple)):
                _fp_update(h, g, seen)
            else:
                h.update(type(g).__qualname__.encode())
    elif isinstance(obj, types.MethodType):
        _fp_update(h, obj.__func__, seen)
        _fp_update(h, obj.__self__, seen)
    elif isinstance(obj, types.CodeType):
        h.update(obj.co_code)
        # co_names disambiguates same-bytecode bodies that differ only in
        # which attribute/global they reference (exp vs log); consts recurse
        # fully so nested lambdas/comprehensions hash their own literals too
        h.update(" ".join(obj.co_names).encode())
        for c in obj.co_consts:
            _fp_update(h, c, seen)
    elif dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        for f in dataclasses.fields(obj):
            h.update(f.name.encode())
            _fp_update(h, getattr(obj, f.name), seen)
    else:
        r = repr(obj)
        if " at 0x" in r:
            # a default repr is just class+address: hashing it would quietly
            # turn content-addressing back into address-keying (without even
            # the old scheme's liveness pin). Hash the instance dict when
            # there is one; otherwise refuse rather than alias.
            d = getattr(obj, "__dict__", None)
            if d:
                if id(obj) in seen:
                    h.update(b"<cycle>")
                    return
                seen.add(id(obj))
                h.update(type(obj).__qualname__.encode())
                _fp_update(h, dict(d), seen)
            else:
                raise TypeError(
                    f"fingerprint: cannot content-hash {type(obj)!r} "
                    "(address-based repr and no __dict__)")
        else:
            h.update(r.encode())


_FP_MEMO_ATTR = "_fingerprint_memo"


def fingerprint(*objs: Any) -> str:
    """Content-addressed digest of ``objs`` for driver-cache keys.

    Two separately-built objects with identical contents map to the SAME
    key (so rebuilding an identical Problem per call still hits the cache),
    and objects that differ anywhere a jitted closure could observe them —
    array data, closure constants, hyperparameters — map to different keys
    even if one is constructed at the other's recycled address.

    Hashing is O(bytes of captured arrays) — for a Problem that is a D2H
    copy + SHA256 of the (d, n) data matrix — so a single frozen-dataclass
    argument memoizes its digest on the instance: repeated runs over one
    large Problem hash it once. (Sound because frozen dataclasses over
    immutable jax arrays cannot change content; a dataclass with mutable
    np fields mutated in place would need the memo cleared.)
    """
    def memoizable(o):
        # only FROZEN dataclasses: a mutable one could change content after
        # the memo was written and silently return a stale digest
        return (dataclasses.is_dataclass(o) and not isinstance(o, type)
                and type(o).__dataclass_params__.frozen)

    if len(objs) == 1 and memoizable(objs[0]):
        memo = getattr(objs[0], _FP_MEMO_ATTR, None)
        if memo is not None:
            return memo
    h = hashlib.sha256()
    seen: set = set()
    for o in objs:
        _fp_update(h, o, seen)
    digest = h.hexdigest()
    if len(objs) == 1 and memoizable(objs[0]):
        try:
            object.__setattr__(objs[0], _FP_MEMO_ATTR, digest)
        except (AttributeError, TypeError):  # __slots__ etc. — just rehash
            pass
    return digest


def clear_driver_cache() -> None:
    """Drop all cached drivers (and the Problems/executables their closures
    pin). Call between large sweeps that build many distinct problems."""
    _DRIVER_CACHE.clear()


# Retrace accounting: every cached_driver resolution is counted (and
# broadcast to listeners) so the analysis retrace detector and the bench
# harness can tell "slow because the engine regressed" apart from "slow
# because an unstable cache key forced a re-trace+re-compile every run".
_CACHE_STATS = {"hits": 0, "misses": 0, "bypass": 0}
_CACHE_LISTENERS: list = []


def driver_cache_stats(reset: bool = False) -> dict:
    """Snapshot of {hits, misses, bypass} cached_driver resolutions since
    process start (or the last ``reset=True`` call)."""
    out = dict(_CACHE_STATS)
    if reset:
        for k in _CACHE_STATS:
            _CACHE_STATS[k] = 0
    return out


def _cache_event(key, kind: str) -> None:
    _CACHE_STATS[kind] += 1
    for listener in list(_CACHE_LISTENERS):
        listener(key, kind)


@contextlib.contextmanager
def cache_listener(fn: Callable[[Any, str], None]):
    """Register ``fn(key, kind)`` for cache events, removably.

    The one sanctioned way to observe ``cached_driver`` resolutions:
    the listener is appended on entry and removed on exit even if the body
    raises, so nested monitors (``analysis.RetraceMonitor``, ``obs.trace``
    tracers) never double-count or leak a stale callback across tests. The
    same function object may be registered by nested scopes — each exit
    removes exactly one registration (list.remove drops the first match,
    which is equivalent for identical callbacks).
    """
    _CACHE_LISTENERS.append(fn)
    try:
        yield fn
    finally:
        try:
            _CACHE_LISTENERS.remove(fn)
        except ValueError:  # already removed (e.g. test cleared the list)
            pass


def cached_driver(key, build: Callable[[], Any]) -> Any:
    """Return (building on miss) the jitted driver for ``key``.

    ``key`` must uniquely determine the semantics AND closure constants of
    the built function — use ``fingerprint()`` for captured objects (NEVER
    ``id()``: a rebuilt object at a recycled address would silently reuse
    the wrong compiled driver). ``key=None`` bypasses the cache.
    """
    if key is None:
        _cache_event(None, "bypass")
        return build()
    fn = _DRIVER_CACHE.get(key)
    if fn is None:
        _cache_event(key, "misses")
        fn = build()
        _DRIVER_CACHE[key] = fn
        if len(_DRIVER_CACHE) > _DRIVER_CACHE_SIZE:
            _DRIVER_CACHE.popitem(last=False)
    else:
        _cache_event(key, "hits")
        _DRIVER_CACHE.move_to_end(key)
    return fn


class BlockRunResult(NamedTuple):
    state: Any
    metrics: np.ndarray | None  # (R, m) rows for rounds where record_mask
    aux: Any                    # per-round step outputs stacked over T, or None
    # (R,) round indices of the metric rows — truncated at the stop round
    # when the recorder's stop condition fired
    rounds: np.ndarray | None = None
    stop_round: int | None = None  # round that certified/stopped, or None


def _num_rounds(schedule: Any, record_mask: np.ndarray | None,
                num_rounds: int | None) -> int:
    if num_rounds is not None:
        return int(num_rounds)
    if record_mask is not None:
        return int(np.shape(record_mask)[0])
    leaves = jax.tree.leaves(schedule)
    if not leaves:
        raise ValueError("cannot infer the round count: pass num_rounds, a "
                         "record_mask, or a schedule with (T, ...) leaves")
    return int(leaves[0].shape[0])


def lift_constants(fn: Callable, *example_args,
                   data: tuple = ()) -> tuple[Callable, list]:
    """Trace ``fn`` once; return ``(lifted, consts)`` such that
    ``lifted(consts, *args)`` computes ``fn(*data, *args)``.

    A jitted program bakes the arrays its function closes over into the
    executable as constants. A recorder closes over the data matrix and its
    node blocks, which at the paper's data sizes are gigabytes — past what
    an executable can embed. Lifted, they enter the block program as
    arguments, like the ``context``. ``data`` (arrays or
    ``jax.ShapeDtypeStruct``s) are ``fn``'s leading arguments and lead
    ``consts``: that is how a recorder is lifted from shapes alone.
    """
    closed, out_shape = jax.make_jaxpr(fn, return_shape=True)(
        *data, *example_args)
    out_tree = jax.tree.structure(out_shape)
    n_data = len(jax.tree.leaves(data))

    def lifted(consts, *args):
        out = jcore.jaxpr_as_fun(
            jcore.ClosedJaxpr(closed.jaxpr, consts[n_data:]))(
                *consts[:n_data], *jax.tree.leaves(args))
        return jax.tree.unflatten(out_tree, out)

    return lifted, jax.tree.leaves(data) + list(closed.consts)


def _round_slice_shapes(schedule: Any, stream: Callable | None) -> Any:
    """Shapes of one round's schedule slice, streamed entries merged in."""
    shapes = jax.tree.map(
        lambda x: jax.ShapeDtypeStruct(
            np.shape(x)[1:], jax.dtypes.canonicalize_dtype(x.dtype)),
        schedule)
    if stream is not None:
        shapes = {**shapes, **jax.eval_shape(stream, jnp.int32(0))}
    return shapes


def _record_shape_key(state: Any, schedule: Any) -> tuple:
    """Hashable shapes of the state and of one round's schedule slice: what
    the recorder's trace in ``lift_constants`` depends on. Cheap, since it
    runs on every call, cache hits included."""
    s_leaves, s_def = jax.tree.flatten(state)
    k_leaves, k_def = jax.tree.flatten(schedule)
    return (s_def, k_def,
            tuple((np.shape(x), np.dtype(x.dtype)) for x in s_leaves),
            tuple((np.shape(x)[1:], np.dtype(x.dtype)) for x in k_leaves))


def block_program(step_fn: Callable[[Any, Any, Any], tuple[Any, Any]],
                  lifted_rec: Callable | None = None, *,
                  stop_fn: Callable | None = None,
                  cadence: Any = None, ratio_fn: Callable | None = None,
                  stream: Callable | None = None) -> Callable:
    """The jitted block program ``run_round_blocks`` dispatches.

    ``lifted_rec``: the recorder as ``lift_constants`` returns it (None:
    no recording); its constants ride the context, which the program takes
    as ``(context, recorder constants)``. The signature depends on the
    arguments: ``(state, ctx, sched, rec)`` without a stop or a stream,
    ``t_idx`` appended with a stream; ``((state, stopped), ctx, sched, rec
    [, t_idx])`` with ``stop_fn``; ``((state, stopped, next, every), ctx,
    sched, t_idx, force)`` with an adaptive ``cadence`` (which needs
    ``ratio_fn``). The state is donated.
    """
    has_stop = stop_fn is not None
    has_cadence = cadence is not None and lifted_rec is not None

    # every block program takes ctx = (context, recorder constants)
    def rec_call(s, sched_t, ctx):
        return lifted_rec(ctx[1], s, sched_t)

    def zero_row(s, sched_t, ctx):
        sd = jax.eval_shape(rec_call, s, sched_t, ctx)
        return jnp.zeros(sd.shape, sd.dtype)

    def skip_step(s, ctx, sched_t):
        # post-certification rounds are no-ops: state passes through
        # untouched, which is what makes the stopped run's final state
        # bitwise equal to the full run's state at the stop round
        aux_sd = jax.eval_shape(lambda ss: step_fn(ss, ctx[0], sched_t)[1],
                                s)
        return s, jax.tree.map(
            lambda sd: jnp.zeros(sd.shape, sd.dtype), aux_sd)

    if has_cadence:
        base = jnp.int32(cadence.base)
        grow = jnp.int32(cadence.grow)
        max_e = jnp.int32(cadence.max_every)
        near = jnp.float32(cadence.near)

        @partial(jax.jit, donate_argnums=(0,))
        def run_block_adaptive(carry0, ctx, sched, t_idx, force):
            def body(carry, xs):
                s, stopped, nxt, every = carry
                sched_t, t, force_t = xs
                if stream is not None:
                    sched_t = {**sched_t, **stream(t)}
                s, aux = lax.cond(
                    stopped, lambda ss: skip_step(ss, ctx, sched_t),
                    lambda ss: step_fn(ss, ctx[0], sched_t), s)
                due = jnp.logical_or(t >= nxt, force_t)
                do_rec = jnp.logical_and(due, jnp.logical_not(stopped))
                row = lax.cond(do_rec,
                               lambda ss: rec_call(ss, sched_t, ctx),
                               lambda ss: zero_row(ss, sched_t, ctx), s)
                # geometric back-off while far from the stop threshold,
                # snap to base inside the near band; the zero row of a
                # non-record round is discarded by the where() gates
                far = ratio_fn(row).astype(jnp.float32) > near
                new_every = jnp.where(
                    far, jnp.minimum(every * grow, max_e), base)
                every = jnp.where(do_rec, new_every, every)
                nxt = jnp.where(do_rec, t + new_every, nxt)
                if stop_fn is not None:
                    stop_now = jnp.logical_and(do_rec, stop_fn(row))
                    stopped = jnp.logical_or(stopped, stop_now)
                return (s, stopped, nxt, every), (aux, row, do_rec)
            return lax.scan(body, carry0, (sched, t_idx, force))

        return run_block_adaptive

    if not has_stop:
        if stream is None:
            # historical engine: no stop carry, no cond around the
            # step — byte-identical program to the pre-recorder
            # executor, which is what keeps GapRecorder histories
            # bitwise reproducible
            @partial(jax.jit, donate_argnums=(0,))
            def run_block(st, ctx, sched, rec):
                def body(s, xs):
                    sched_t, rec_t = xs
                    s, aux = step_fn(s, ctx[0], sched_t)
                    if lifted_rec is None:
                        return s, (aux, None)
                    row = lax.cond(rec_t,
                                   lambda ss: rec_call(ss, sched_t, ctx),
                                   lambda ss: zero_row(ss, sched_t, ctx), s)
                    return s, (aux, row)
                return lax.scan(body, st, (sched, rec))

            return run_block

        @partial(jax.jit, donate_argnums=(0,))
        def run_block_streamed(st, ctx, sched, rec, t_idx):
            def body(s, xs):
                sched_t, rec_t, t = xs
                sched_t = {**sched_t, **stream(t)}
                s, aux = step_fn(s, ctx[0], sched_t)
                if lifted_rec is None:
                    return s, (aux, None)
                row = lax.cond(rec_t,
                               lambda ss: rec_call(ss, sched_t, ctx),
                               lambda ss: zero_row(ss, sched_t, ctx), s)
                return s, (aux, row)
            return lax.scan(body, st, (sched, rec, t_idx))

        return run_block_streamed

    @partial(jax.jit, donate_argnums=(0,))
    def run_block_stop(carry0, ctx, sched, rec, t_idx=None):
        def body(carry, xs):
            s, stopped = carry
            if stream is None:
                sched_t, rec_t = xs
            else:
                sched_t, rec_t, t = xs
                sched_t = {**sched_t, **stream(t)}

            s, aux = lax.cond(
                stopped, lambda ss: skip_step(ss, ctx, sched_t),
                lambda ss: step_fn(ss, ctx[0], sched_t), s)
            do_rec = jnp.logical_and(rec_t, jnp.logical_not(stopped))
            row = lax.cond(do_rec,
                           lambda ss: rec_call(ss, sched_t, ctx),
                           lambda ss: zero_row(ss, sched_t, ctx), s)
            stop_now = jnp.logical_and(do_rec, stop_fn(row))
            return (s, jnp.logical_or(stopped, stop_now)), \
                (aux, row, do_rec)
        xs = (sched, rec) if stream is None else (sched, rec, t_idx)
        return lax.scan(body, carry0, xs)

    return run_block_stop


def run_round_blocks(step_fn: Callable[[Any, Any, Any], tuple[Any, Any]],
                     state: Any, schedule: Any, *,
                     context: Any = None,
                     recorder: Any = None,
                     record_mask: np.ndarray | None = None,
                     block_size: int = 64,
                     num_rounds: int | None = None,
                     cache_key: Any = None,
                     cadence: Any = None,
                     stream: Callable | None = None) -> BlockRunResult:
    """Run ``T`` rounds of ``step_fn`` in ceil(T / block_size) dispatches.

    Args:
      step_fn: ``(state, context, sched_t) -> (state, aux)`` — the pure round
        body. ``sched_t`` is the per-round slice of ``schedule``; ``aux`` is
        an optional per-round output pytree (or None).
      state: carried state pytree; its buffers are donated to the scan.
      schedule: pytree of ``(T, ...)`` arrays (host numpy is fine — each
        block's slice is shipped to the device at dispatch). May be empty
        (``{}``) when the round body needs no per-round inputs.
      context: run-constant pytree (e.g. the CoLA env) passed through to
        ``step_fn`` as a jit argument so large arrays are not baked into the
        executable as constants.
      recorder: a ``repro.core.metrics`` Recorder — its ``record_fn`` is
        evaluated on device for rounds where ``record_mask`` is set, and its
        ``stop_fn`` (when not None) arms early exit: the round whose row
        satisfies the stop condition is the LAST live round — the remaining
        rounds of its block are ``lax.cond`` no-ops and subsequent block
        dispatches are skipped host-side. Early exit costs one scalar device
        sync per block (the stop flag read); without a stop_fn the engine
        keeps the historical fully-async single-fetch behaviour and the
        identical compiled program.
      record_mask: ``(T,)`` bool — which rounds record a metric row.
      block_size: rounds per device dispatch. At most two program shapes are
        compiled (full block + remainder).
      num_rounds: explicit T when neither schedule nor record_mask carries it.
      cache_key: when set, the jitted block program is reused across calls
        (see ``cached_driver``) so repeated runs skip trace+compile. The key
        must pin down ``step_fn``/recorder semantics and captured constants —
        use ``fingerprint()`` for closed-over objects and the recorder's
        ``cache_token()``. A ``cadence`` is appended to the key
        automatically.
      stream: optional pure-jax generator ``t -> {entry: array}`` (see
        ``repro.core.schedule.ScheduleProgram.stream_fn``) evaluated INSIDE
        the scan body: its output merges over the round's ``schedule``
        slice (streamed entries win) before the step function and the
        recorder see it. This is what lets per-round inputs that are
        cheap to re-derive (participation masks, sampled mixing matrices,
        attack transform rows — anything keyed by ``fold_in(t)``) avoid
        (T, ...) host materialization entirely; ``schedule`` must then be
        a dict and may be empty. The generator is folded into the driver
        cache key automatically. ``stream=None`` programs are
        byte-identical to the historical executor.
      cadence: a ``repro.core.metrics.AdaptiveCadence`` — replaces the
        host-side ``record_mask`` with an ON-DEVICE record controller: the
        next record round and current cadence ride the scan carry, each
        recorded row's ``recorder.cadence_ratio`` geometrically backs the
        cadence off while far from the stop threshold and snaps it to
        ``base`` inside the near band. Stop short-circuiting (block no-ops
        + host-side skip) is unchanged; the last round always records.

    Returns:
      BlockRunResult(state, metrics, aux, rounds, stop_round): ``metrics``
      holds the recorded rows only (record_mask applied, truncated at the
      stop round), fetched in a single device sync at the end; ``rounds``
      are the corresponding round indices; ``aux`` stacks the per-round step
      outputs over all executed rounds (no-op rounds after a stop contribute
      zeros).
    """
    t_total = _num_rounds(schedule, record_mask, num_rounds)
    if stream is not None:
        if not isinstance(schedule, dict):
            raise TypeError(
                "stream= requires a dict schedule: streamed entries merge "
                f"into the per-round slice (got {type(schedule).__name__})")
        # the generator's bytecode + closure are part of the compiled
        # program's content, exactly like the step function's
        cache_key = (None if cache_key is None
                     else (cache_key, ("stream", fingerprint(stream))))
    record_fn = recorder.record_fn if recorder is not None else None
    stop_fn = recorder.stop_fn if recorder is not None else None
    # schedule-aware recorders (e.g. the dynamic churn certificate) receive
    # the round's schedule slice alongside the state
    uses_sched = bool(getattr(recorder, "uses_schedule", False))
    has_cadence = cadence is not None and record_fn is not None
    if has_cadence:
        cache_key = (None if cache_key is None
                     else (cache_key, cadence.cache_token()))
    if record_fn is not None and record_mask is None and not has_cadence:
        record_mask = np.ones((t_total,), dtype=bool)
    rec_all = (np.asarray(record_mask, dtype=bool)
               if record_fn is not None and not has_cadence
               else np.zeros((t_total,), dtype=bool))
    has_stop = stop_fn is not None
    if record_fn is not None and cache_key is not None:
        # the recorder is traced for these shapes (``lift_constants``): a
        # cached program is reused only where that trace fits (the stream's
        # shapes follow from its fingerprint, already in the key)
        cache_key = (cache_key, ("record-shapes",
                                 _record_shape_key(state, schedule)))

    # phase tracing (repro.obs.trace): the active tracer records the driver
    # build (trace time — runs only on a cache miss/bypass) and every block
    # dispatch. The first dispatch span absorbs the XLA compile; steady
    # blocks measure dispatch (+ the per-block stop-flag sync when early
    # exit is armed). Lazy import: obs.trace imports this module.
    from repro.obs import trace as obs_trace
    tracer = obs_trace.current()

    def timed_build():
        with tracer.span("driver-build", key=cache_key is not None):
            lifted_rec, rec_consts = None, []
            if record_fn is not None:
                # a cache hit reuses these arrays: the content-addressed key
                # makes them equal to the ones this call's recorder holds
                lifted_rec, rec_consts = lift_constants(
                    lambda s, sched_t: (record_fn(s, sched_t) if uses_sched
                                        else record_fn(s)),
                    state, _round_slice_shapes(schedule, stream))
            program = block_program(
                step_fn, lifted_rec, stop_fn=stop_fn,
                cadence=cadence if has_cadence else None,
                ratio_fn=recorder.cadence_ratio if has_cadence else None,
                stream=stream)
            return program, lifted_rec, rec_consts

    run_block, lifted_rec, rec_consts = cached_driver(cache_key, timed_build)
    ctx_arg = (context, rec_consts)

    rows, valids, auxes = [], [], []
    start = 0
    executed = 0
    n_dispatch = 0
    stopped_early = False
    with warnings.catch_warnings():
        if jax.default_backend() == "cpu":
            # donation is a no-op on CPU, so the warning is pure noise there;
            # on accelerators it signals real aliasing bugs — keep it
            warnings.filterwarnings("ignore", message=".*donated.*")
        stop_flag = jnp.asarray(False)
        # adaptive carry: (state, stopped, next-record round, cadence) — the
        # controller state persists across block dispatches like the state
        carry = (state, stop_flag, jnp.int32(0),
                 jnp.int32(cadence.base)) if has_cadence else None
        while start < t_total:
            stop = min(start + block_size, t_total)
            span_name = ("block-first-dispatch" if n_dispatch == 0
                         else "block-dispatch")
            n_dispatch += 1
            with tracer.span(span_name, start=start, rounds=stop - start):
                sched_b = jax.tree.map(lambda x: jnp.asarray(x[start:stop]),
                                       schedule)
                if has_cadence:
                    t_b = jnp.arange(start, stop, dtype=jnp.int32)
                    force_b = jnp.asarray(
                        np.arange(start, stop) == t_total - 1)
                    carry, (aux_b, rows_b, valid_b) = run_block(
                        carry, ctx_arg, sched_b, t_b, force_b)
                    state, stop_flag = carry[0], carry[1]
                    valids.append(valid_b)
                elif has_stop:
                    args = ((state, stop_flag), ctx_arg, sched_b,
                            jnp.asarray(rec_all[start:stop]))
                    if stream is not None:
                        args += (jnp.arange(start, stop, dtype=jnp.int32),)
                    (state, stop_flag), (aux_b, rows_b, valid_b) = \
                        run_block(*args)
                    valids.append(valid_b)
                else:
                    args = (state, ctx_arg, sched_b,
                            jnp.asarray(rec_all[start:stop]))
                    if stream is not None:
                        args += (jnp.arange(start, stop, dtype=jnp.int32),)
                    state, (aux_b, rows_b) = run_block(*args)
                if rows_b is not None:
                    rows.append(rows_b)
                if aux_b is not None and jax.tree.leaves(aux_b):
                    auxes.append(aux_b)
                start = stop
                executed = stop
                # the host-side short-circuit: one scalar sync per block,
                # only when early exit is armed
                if has_stop and bool(stop_flag):
                    stopped_early = True
                    break

    metrics = rounds = None
    stop_round = None
    if record_fn is not None:
        if (has_stop or has_cadence) and valids:
            valid = np.concatenate([np.asarray(v) for v in valids], axis=0)
        else:
            valid = rec_all[:executed]
        if rows:
            metrics = np.concatenate([np.asarray(r) for r in rows],
                                     axis=0)[valid]
            rounds = np.nonzero(valid)[0]
        else:  # T == 0: empty history, same as the loop drivers
            row_sd = jax.eval_shape(lifted_rec, rec_consts, state,
                                    _round_slice_shapes(schedule, stream))
            metrics = np.zeros((0,) + row_sd.shape, row_sd.dtype)
            rounds = np.zeros((0,), dtype=np.int64)
        if stopped_early and rounds.size:
            stop_round = int(rounds[-1])
    aux = None
    if auxes:
        aux = jax.tree.map(lambda *xs: np.concatenate(
            [np.asarray(x) for x in xs], axis=0), *auxes)
    return BlockRunResult(state=state, metrics=metrics, aux=aux,
                          rounds=rounds, stop_round=stop_round)


def make_block_runner(step_fn: Callable, *, recorder: Any = None,
                      block_size: int = 64,
                      cache_key: Any = None) -> Callable:
    """Bind a round body and a Recorder into a reusable block runner.

    Returns ``run(state, schedule, *, context=None, record_mask=None,
    num_rounds=None) -> BlockRunResult`` — ``run_round_blocks`` with the
    recorder/engine knobs fixed, the shape all four drivers consume.
    """
    def run(state, schedule, *, context=None, record_mask=None,
            num_rounds=None):
        return run_round_blocks(
            step_fn, state, schedule, context=context, recorder=recorder,
            record_mask=record_mask, block_size=block_size,
            num_rounds=num_rounds, cache_key=cache_key)

    return run


def record_flags(rounds: int, record_every: int) -> np.ndarray:
    """The driver-loop recording pattern: every ``record_every``-th round and
    always the last one."""
    t = np.arange(rounds)
    return (t % record_every == 0) | (t == rounds - 1)
