"""Seeded-violation fixtures: prove every verifier pass actually fires.

A static checker that silently passes on everything is worse than none, so
each pass ships with a deliberately broken program — a forced f64 upcast, a
``jax.debug.print`` inside a scan, an injected all-gather on the plan path,
a donation with no usable output, an oversized captured constant, an
unstable cache key, and source snippets violating each AST rule. The CLI's
``--selftest`` (and ``tests/test_analysis.py``) runs them all and FAILS if
any seeded violation goes undetected.

Each fixture returns the findings its pass produced on the broken program;
"caught" means at least one finding names the seeded defect.
"""
from __future__ import annotations

import itertools
import textwrap
import warnings
from typing import Callable, List

from repro.analysis import astlint, passes
from repro.analysis.passes import Finding

SELFTESTS: dict = {}


def register_selftest(name: str) -> Callable:
    def deco(fn):
        SELFTESTS[name] = fn
        fn.selftest_name = name
        return fn
    return deco


@register_selftest("dtype-drift")
def seeded_dtype_drift() -> List[Finding]:
    """A silent f32 -> f64 -> f32 round-trip inside the program."""
    import jax
    import jax.numpy as jnp

    with jax.enable_x64(True):
        def fn(x):
            acc = x.astype(jnp.float64) * 2.0
            return acc.astype(jnp.float32)
        closed = jax.make_jaxpr(fn)(jnp.zeros((4,), jnp.float32))
    return passes.dtype_drift(closed, where="selftest:f64-upcast")


@register_selftest("host-callback-in-scan")
def seeded_host_callback() -> List[Finding]:
    """A forgotten ``jax.debug.print`` inside the round scan."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def fn(x):
        def step(carry, _):
            jax.debug.print("round carry {c}", c=carry)
            return carry + 1.0, None
        return lax.scan(step, x, None, length=3)[0]

    closed = jax.make_jaxpr(fn)(jnp.float32(0.0))
    return passes.host_callback_in_scan(closed,
                                        where="selftest:debug-print")


@register_selftest("constant-capture")
def seeded_constant_capture() -> List[Finding]:
    """A 2 MiB array baked into the jaxpr instead of passed as an arg."""
    import jax
    import jax.numpy as jnp

    big = jnp.ones((1 << 19,), jnp.float32)  # 2 MiB

    def fn(x):
        return x + big.sum()

    closed = jax.make_jaxpr(fn)(jnp.float32(0.0))
    return passes.constant_capture(closed, max_bytes=1 << 20,
                                   where="selftest:2MiB-const")


@register_selftest("donation")
def seeded_donation() -> List[Finding]:
    """A donated buffer with no shape-matching output: jax drops the
    donation with only a warning; the pass must treat it as a violation."""
    import jax.numpy as jnp

    def fn(x):
        return x[: x.shape[0] // 2] * 2.0  # no (8,) output to alias into

    args = (jnp.zeros((8,), jnp.float32),)
    with warnings.catch_warnings():
        warnings.simplefilter("ignore")  # jax's "donation not used" warning
        return passes.donation(fn, args, (0,),
                               where="selftest:unusable-donation")


@register_selftest("retrace")
def seeded_retrace() -> List[Finding]:
    """An unstable cache key (fresh every call): the warmed re-run must
    surface as cache misses."""
    from repro.core import executor

    counter = itertools.count()

    def run():
        executor.cached_driver(("selftest-retrace", next(counter)),
                               lambda: (lambda: None))

    findings = passes.check_retrace(run, where="selftest:unstable-key")
    executor.clear_driver_cache()
    return findings


@register_selftest("comm-contract")
def seeded_all_gather() -> List[Finding]:
    """An all-gather injected into the plan-executed round: the compiled
    HLO must violate the plan's neighbor-only contract. Needs a 4-device
    mesh (raises ``drivers.SkipDriver`` otherwise)."""
    from repro.analysis import contracts, drivers
    from repro.core import topology as topo

    prob = drivers._lasso()
    hlo, plan = drivers.plan_round_hlo(prob, topo.torus_2d(2, 2), 4,
                                      inject_all_gather=True)
    try:
        contracts.check_comm(hlo, plan.contract(prob.d))
    except contracts.CommContractViolation as e:
        return [Finding("comm-contract", str(e),
                        where="selftest:injected-all-gather")]
    return []


@register_selftest("comm-contract-wire")
def seeded_fp32_leak() -> List[Finding]:
    """An fp32 payload ppermuted across a claimed-int8 wire: the narrow
    contract's byte cap must catch the wide leak. Needs a 4-device mesh
    (raises ``drivers.SkipDriver`` otherwise)."""
    from repro.analysis import contracts, drivers
    from repro.core import topology as topo

    prob = drivers._lasso()
    hlo, plan = drivers.quant_round_hlo(prob, topo.torus_2d(2, 4), 8, 4,
                                        "int8", inject_fp32_leak=True)
    try:
        contracts.check_comm(hlo, plan.contract(prob.d, wire="int8"))
    except contracts.CommContractViolation as e:
        return [Finding("comm-contract", str(e),
                        where="selftest:fp32-on-int8-wire")]
    return []


@register_selftest("telemetry-carry")
def seeded_telemetry_constant() -> List[Finding]:
    """Telemetry counters captured as a trace-time constant instead of
    extending the round scan's carry: the "on" build's scan carries no more
    state than its off twin, so every counter update is dead code and the
    recorded totals freeze at their trace values."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    def run_off(x):
        def step(carry, _):
            return carry + 1.0, None
        return lax.scan(step, x, None, length=4)[0]

    wire_bytes = jnp.zeros(())  # the seeded bug: counter not in the carry

    def run_on_broken(x):
        def step(carry, _):
            _ = wire_bytes + 64.0  # "update" that never re-enters the scan
            return carry + 1.0, None
        return lax.scan(step, x, None, length=4)[0]

    off = jax.make_jaxpr(run_off)(jnp.float32(0.0))
    on = jax.make_jaxpr(run_on_broken)(jnp.float32(0.0))
    return passes.telemetry_carry(off, on,
                                  where="selftest:constant-counter")


_AST_VIOLATIONS = {
    "frozen-transform": """
        class Mutable:
            def apply(self, sched, ctx):
                sched["w"] = None
        """,
    "id-in-cache-key": """
        def build_driver(prob, build):
            return cached_driver((id(prob), 3), build)
        """,
    "prng-reuse": """
        def sample(key):
            a = jax.random.normal(key, (3,))
            b = jax.random.uniform(key, (3,))
            return a, b
        """,
}


def _seeded_ast(rule: str) -> Callable[[], List[Finding]]:
    def fixture() -> List[Finding]:
        src = textwrap.dedent(_AST_VIOLATIONS[rule])
        return [f for f in astlint.lint_source(src, f"selftest:{rule}")
                if f.pass_name == rule]
    fixture.__doc__ = f"Source snippet violating the ``{rule}`` AST rule."
    return fixture


for _rule in _AST_VIOLATIONS:
    register_selftest(f"ast-{_rule}")(_seeded_ast(_rule))


def run_selftests(*, skip_mesh: bool = False) -> List[tuple]:
    """Run every seeded violation; returns ``(name, caught, detail)`` rows.

    ``caught`` is True when the pass produced at least one finding on its
    broken program — the CLI exits nonzero on any False. ``skip_mesh``
    marks mesh-dependent fixtures as skipped (``caught=None``) instead of
    erroring on small-device hosts.
    """
    from repro.analysis.drivers import SkipDriver

    rows = []
    for name, fixture in SELFTESTS.items():
        try:
            findings = fixture()
        except SkipDriver as e:
            if skip_mesh:
                rows.append((name, None, str(e)))
                continue
            raise
        caught = len(findings) > 0
        detail = str(findings[0]) if findings else \
            "pass produced NO findings on its seeded violation"
        rows.append((name, caught, detail))
    return rows
