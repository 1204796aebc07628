"""Jaxpr lint passes over COLA driver programs.

Each pass walks a closed jaxpr (or a lowering) and returns a list of
``Finding``s — empty means the contract holds. Passes register under a name
with ``@register_pass`` so the CLI can enumerate them; they are plain
functions, so tests and drivers can also call them directly with
pass-specific keyword knobs.

The contracts (see ``repro.analysis.__init__`` for the full table):

* ``dtype-drift`` — the round hot path is HONEST fp32: every floating-point
  value in the jaxpr has the declared compute dtype. A weak-type promotion
  to f64 or a silent bf16/f16 round-trip both corrupt the certificate
  arithmetic without failing any numeric test until much later.
* ``host-callback-in-scan`` — no host callback primitive (``debug_callback``,
  ``pure_callback``, ``io_callback``, ...) inside a ``scan``/``while`` body:
  one forgotten ``jax.debug.print`` forces a host sync per round and
  destroys the block executor's dispatch amortization.
* ``constant-capture`` — no closed-over array above a size threshold baked
  into the program as a jaxpr constant: large constants bloat every cached
  executable and make ``executor.fingerprint`` hash the captured bytes on
  every cache probe.
* ``donation`` — every arg declared in ``donate_argnums`` is actually
  marked for aliasing in the lowering (``tf.aliasing_output`` /
  ``jax.buffer_donor``): a donated buffer that silently fails to alias
  doubles the state memory of long runs.
* ``retrace`` (``check_retrace`` / ``RetraceMonitor``) — a warmed-up run
  must resolve every ``executor.cached_driver`` probe as a hit: a miss on
  the second identical run means the cache key is unstable and every run
  pays trace+compile.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, List

import jax
import numpy as np
from jax.extend import core as jcore

from repro.core import executor


@dataclasses.dataclass(frozen=True)
class Finding:
    """One contract violation: which pass, where, and what went wrong."""

    pass_name: str
    message: str
    where: str = ""

    def __str__(self) -> str:
        loc = f" [{self.where}]" if self.where else ""
        return f"{self.pass_name}{loc}: {self.message}"


PASS_REGISTRY: dict = {}


def register_pass(name: str) -> Callable:
    """Register a pass under ``name`` (listed by the CLI; see module
    docstring for the contract each built-in pass enforces)."""
    def deco(fn):
        PASS_REGISTRY[name] = fn
        fn.pass_name = name
        return fn
    return deco


# -- jaxpr walking ----------------------------------------------------------

def _sub_jaxprs(eqn) -> Iterator:
    for val in eqn.params.values():
        vals = val if isinstance(val, (tuple, list)) else (val,)
        for v in vals:
            if isinstance(v, jcore.ClosedJaxpr):
                yield v.jaxpr
            elif isinstance(v, jcore.Jaxpr):
                yield v


def walk_eqns(jaxpr, path: tuple = ()) -> Iterator[tuple]:
    """Yield (eqn, path) over ``jaxpr`` and every nested sub-jaxpr, where
    ``path`` is the tuple of enclosing primitive names (scan, cond, ...)."""
    for eqn in jaxpr.eqns:
        yield eqn, path
        for sub in _sub_jaxprs(eqn):
            yield from walk_eqns(sub, path + (eqn.primitive.name,))


def _closed(jaxpr_or_fn, *args):
    if isinstance(jaxpr_or_fn, jcore.ClosedJaxpr):
        return jaxpr_or_fn
    return jax.make_jaxpr(jaxpr_or_fn)(*args)


# -- passes -----------------------------------------------------------------

@register_pass("dtype-drift")
def dtype_drift(closed: jcore.ClosedJaxpr, *, compute_dtype="float32",
                where: str = "") -> List[Finding]:
    """Flag every floating/complex value whose dtype differs from the
    declared compute dtype — weak-type f64 promotions, silent f32->f64
    upcasts and lossy bf16/f16 round-trips all surface here."""
    import jax.numpy as jnp
    compute = np.dtype(compute_dtype)
    out: List[Finding] = []
    seen: set = set()

    def check(aval, label, path):
        dt = getattr(aval, "dtype", None)
        if dt is None:
            return
        if not (jnp.issubdtype(dt, jnp.floating)
                or jnp.issubdtype(dt, jnp.complexfloating)):
            return
        if np.dtype(dt) == compute:
            return
        key = (label, str(dt), path)
        if key in seen:
            return
        seen.add(key)
        inside = "/".join(path) or "<top>"
        out.append(Finding(
            "dtype-drift",
            f"{label} has dtype {dt} (compute dtype is {compute}) "
            f"inside {inside}", where))

    for var in closed.jaxpr.invars:
        check(var.aval, "input", ())
    for eqn, path in walk_eqns(closed.jaxpr):
        for var in eqn.outvars:
            check(var.aval, f"{eqn.primitive.name} output", path)
    return out


_CALLBACK_PRIMS = frozenset({
    "debug_callback", "pure_callback", "io_callback", "callback",
    "outside_call", "host_callback_call", "debug_print"})
_LOOP_PRIMS = frozenset({"scan", "while"})


@register_pass("host-callback-in-scan")
def host_callback_in_scan(closed: jcore.ClosedJaxpr, *,
                          where: str = "") -> List[Finding]:
    """Flag host-callback primitives inside scan/while bodies — each one is
    a per-round host round-trip in the block executor."""
    out: List[Finding] = []
    for eqn, path in walk_eqns(closed.jaxpr):
        if eqn.primitive.name in _CALLBACK_PRIMS \
                and any(p in _LOOP_PRIMS for p in path):
            out.append(Finding(
                "host-callback-in-scan",
                f"{eqn.primitive.name} inside {'/'.join(path)}: a host "
                "sync every loop iteration defeats the round-block "
                "dispatch amortization", where))
    return out


@register_pass("constant-capture")
def constant_capture(closed: jcore.ClosedJaxpr, *,
                     max_bytes: int = 1 << 20,
                     where: str = "") -> List[Finding]:
    """Flag closed-over array constants above ``max_bytes`` — they belong in
    the executor's ``context`` argument, not baked into the executable."""
    out: List[Finding] = []
    for const in closed.consts:
        try:
            arr = np.asarray(const)
        except Exception:
            continue
        if arr.nbytes > max_bytes:
            out.append(Finding(
                "constant-capture",
                f"captured constant {arr.dtype}{list(arr.shape)} is "
                f"{arr.nbytes:,} bytes (> {max_bytes:,}): pass it as a jit "
                "argument (executor `context`) instead of closing over it",
                where))
    return out


_DONATION_MARKERS = ("tf.aliasing_output", "jax.buffer_donor")


@register_pass("donation")
def donation(fn: Callable, args: tuple, donate_argnums: tuple, *,
             where: str = "") -> List[Finding]:
    """Verify every leaf of the ``donate_argnums`` args is actually marked
    for input/output aliasing in the lowered program. jax drops donations
    it cannot match to an output (shape/dtype mismatch) with only a
    warning — here that is a contract violation."""
    lowered = jax.jit(fn, donate_argnums=donate_argnums).lower(*args)
    text = lowered.as_text()
    expected = sum(len(jax.tree.leaves(args[i])) for i in donate_argnums)
    marked = sum(text.count(m) for m in _DONATION_MARKERS)
    if marked < expected:
        return [Finding(
            "donation",
            f"{expected - marked} of {expected} donated buffers are not "
            "aliased in the lowering (no tf.aliasing_output/"
            "jax.buffer_donor marker): the donation silently fell off and "
            "the state is double-buffered", where)]
    return []


# -- retrace detection ------------------------------------------------------

class RetraceMonitor:
    """Record every ``executor.cached_driver`` resolution in a scope.

    Built on ``executor.cache_listener``, so nesting two monitors (or a
    monitor inside an ``obs.trace`` tracer) registers two independent
    callbacks and each exit removes exactly its own — no double counting,
    no leaked listener after an exception.

    >>> with RetraceMonitor() as mon:
    ...     run()
    >>> mon.misses   # (key, kind) events that re-built a driver
    """

    def __init__(self):
        self.events: list = []
        self._cm = None

    def __enter__(self):
        self._cm = executor.cache_listener(self._on)
        self._cm.__enter__()
        return self

    def __exit__(self, *exc):
        cm, self._cm = self._cm, None
        if cm is not None:
            cm.__exit__(*exc)
        return False

    def _on(self, key, kind: str) -> None:
        self.events.append((key, kind))

    @property
    def misses(self) -> list:
        return [e for e in self.events if e[1] != "hits"]


@register_pass("retrace")
def check_retrace(run_fn: Callable, *, warmups: int = 1,
                  where: str = "") -> List[Finding]:
    """Run ``run_fn`` ``warmups`` times to populate the driver cache, then
    once more under a ``RetraceMonitor``: any miss or bypass on the warmed
    run means the cache key is unstable (or caching is off) and every run
    re-traces."""
    for _ in range(warmups):
        run_fn()
    with RetraceMonitor() as mon:
        run_fn()
    out: List[Finding] = []
    for key, kind in mon.misses:
        what = ("cache bypass (cache_key=None)" if kind == "bypass"
                else f"cache miss on warmed key {key!r}")
        out.append(Finding(
            "retrace",
            f"{what}: the driver re-traced after an identical warm run — "
            "unstable cache key", where))
    return out


@register_pass("telemetry-carry")
def telemetry_carry(closed_off: jcore.ClosedJaxpr,
                    closed_on: jcore.ClosedJaxpr, *,
                    where: str = "") -> List[Finding]:
    """Verify telemetry counters ride the round scan's CARRY.

    Takes the telemetry-off and telemetry-on builds of one round-block
    program. The on-device ``obs.counters`` accumulate per round, so
    enabling telemetry must GROW the carry of (at least) the round scan;
    if no scan in the telemetry-on jaxpr carries more state than the
    largest scan of its off twin, the counters were captured as trace-time
    constants (computed outside the scan, or summed host-side from a baked
    array) and the recorded totals silently freeze at their trace values.
    """
    def max_carry(closed):
        carries = [eqn.params.get("num_carry", 0)
                   for eqn, _ in walk_eqns(closed.jaxpr)
                   if eqn.primitive.name == "scan"]
        return max(carries, default=None)

    off, on = max_carry(closed_off), max_carry(closed_on)
    if on is None:
        return [Finding(
            "telemetry-carry",
            "telemetry-on program contains no scan: counters cannot be "
            "carried per round at all", where)]
    if off is not None and on <= off:
        return [Finding(
            "telemetry-carry",
            f"telemetry-on round scan carries {on} values, no more than "
            f"the telemetry-off twin's {off}: the counters are captured "
            "as constants instead of accumulated in the scan carry",
            where)]
    return []


def run_jaxpr_passes(jaxpr_or_fn, *args, where: str = "",
                     compute_dtype="float32",
                     max_const_bytes: int = 1 << 20) -> List[Finding]:
    """All jaxpr-level passes (dtype-drift, host-callback-in-scan,
    constant-capture) over one program."""
    closed = _closed(jaxpr_or_fn, *args)
    return (dtype_drift(closed, compute_dtype=compute_dtype, where=where)
            + host_callback_in_scan(closed, where=where)
            + constant_capture(closed, max_bytes=max_const_bytes,
                               where=where))
