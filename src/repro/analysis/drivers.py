"""Registered driver configurations for the static verifier.

Every entry in ``DRIVER_REGISTRY`` is a zero-argument check that builds one
representative compiled COLA program — simulator round blocks, dist
ring/plan/block rounds, robust mixing, gossip-DP, certificate recorders —
and holds it to its contracts: comm budgets via ``contracts.check_comm``,
jaxpr lints via ``passes.run_jaxpr_passes``, donation via
``passes.donation``. ``python -m repro.analysis --all`` runs them all.

The ``*_round_hlo`` builders are shared with the dist test files (the tests
migrated their inline HLO construction here), so the program the CLI
verifies is byte-identical to the one the test suite asserts on.

Multi-device note: the dist builders lower shard_map programs for real
meshes (up to 4 devices) — callers without enough devices get a
``SkipDriver`` (the CLI entry point forces 8 virtual CPU devices before
importing jax, so ``python -m repro.analysis`` always runs everything).
"""
from __future__ import annotations

from typing import Callable, List

import numpy as np

from repro.analysis import contracts, passes
from repro.analysis.passes import Finding

DRIVER_REGISTRY: dict = {}


class SkipDriver(RuntimeError):
    """Raised by a driver check whose mesh requirements this process
    cannot satisfy (too few devices)."""


def register_driver(name: str) -> Callable:
    def deco(fn):
        DRIVER_REGISTRY[name] = fn
        fn.driver_name = name
        return fn
    return deco


def _require_devices(n: int) -> None:
    import jax
    if jax.device_count() < n:
        raise SkipDriver(
            f"needs {n} devices, have {jax.device_count()} (run via "
            "`python -m repro.analysis`, which forces a virtual mesh)")


def _lasso(n_samples: int = 150, d: int = 48):
    import jax.numpy as jnp
    from repro.core import problems
    from repro.data import synthetic
    x, y, _ = synthetic.regression(n_samples, d, seed=2,
                                   sparsity_solution=0.2)
    return problems.lasso(jnp.asarray(x), jnp.asarray(y), 5e-2, box=5.0)


def _ridge(n_samples: int = 64, d: int = 32):
    import jax.numpy as jnp
    from repro.core import problems
    from repro.data import synthetic
    x, y, _ = synthetic.regression(n_samples, d, seed=0)
    return problems.ridge_primal(jnp.asarray(x), jnp.asarray(y), 1e-2)


# -- shared HLO builders (used by tests/test_dist_plan.py and
# -- tests/test_certificate_dist.py after their migration) ------------------

def plan_round_hlo(prob, graph, k: int, *, inject_all_gather: bool = False):
    """Compiled HLO of the per-node plan-executed round (one node per
    device) plus its ``CommPlan``. ``inject_all_gather`` plants a live
    all-gather in the round body — the seeded violation the CI smoke
    asserts the verifier catches."""
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import topo as rtopo
    from repro.core import topology as topo
    from repro.core.cola import ColaConfig, _round_body, build_env, \
        init_state
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import (cola_env_pspecs, cola_state_pspecs,
                                     plan_payload_pspecs)

    _require_devices(k)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    mesh = jax.make_mesh((k,), ("data",))
    plan = rtopo.compile_plan(graph)
    cfg = ColaConfig(kappa=1.0)
    mix_fn, grad_mix_fn = rt._dist_mixers("data", 1, 1, "plan",
                                          cfg.gossip_steps, plan)
    body = _round_body(prob, part, cfg, mix_fn=mix_fn,
                      grad_mix_fn=grad_mix_fn)

    def round_fn(st, e, pay, act):
        new = body(st, e, pay, act)
        if inject_all_gather:
            # a live (gradient-relevant) gather of the stack: exactly the
            # O(K*d) traffic the plan path exists to avoid
            leak = lax.all_gather(new.v_stack, "data").sum() \
                * jnp.float32(1e-30)
            new = jax.tree.map(lambda a: a + leak, new)
        return new

    state_spec, env_spec = cola_state_pspecs("data"), cola_env_pspecs("data")
    shard_step = jax.shard_map(
        round_fn, mesh=mesh,
        in_specs=(state_spec, env_spec, plan_payload_pspecs("data"),
                  P("data")),
        out_specs=state_spec)

    w = topo.metropolis_weights(graph)
    diag, coefs = rtopo.plan_coefficients(plan, w)
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    args = (jax.tree.map(sds, init_state(prob, part)),
            jax.tree.map(sds, env),
            (sds(diag.astype(np.float32)), sds(coefs.astype(np.float32))),
            sds(np.ones(k, np.float32)))
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree.map(lambda _: sh(state_spec), args[0]),
             jax.tree.map(lambda _: sh(env_spec), args[1]),
             (sh(P("data")), sh(P(None, "data"))), sh(P("data")))
    hlo = jax.jit(shard_step, in_shardings=in_sh) \
        .lower(*args).compile().as_text()
    return hlo, plan


def dense_round_hlo(prob, graph, k: int) -> str:
    """Compiled HLO of the dense all-gather oracle round — the contrast
    program that MUST move the (K, d) stack."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import topology as topo
    from repro.core.cola import ColaConfig, _round_body, build_env, \
        init_state
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import cola_env_pspecs, cola_state_pspecs

    _require_devices(k)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    mesh = jax.make_mesh((k,), ("data",))
    cfg = ColaConfig(kappa=1.0)
    mix_d, grad_d = rt._dist_mixers("data", 1, 1, "dense", cfg.gossip_steps)
    body_d = _round_body(prob, part, cfg, mix_fn=mix_d, grad_mix_fn=grad_d)
    state_spec, env_spec = cola_state_pspecs("data"), cola_env_pspecs("data")
    shard_d = jax.shard_map(
        lambda st, e, w_, act: body_d(st, e, w_, act), mesh=mesh,
        in_specs=(state_spec, env_spec, P(), P("data")),
        out_specs=state_spec)
    w = topo.metropolis_weights(graph)
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    args = (jax.tree.map(sds, init_state(prob, part)),
            jax.tree.map(sds, env), sds(w.astype(np.float32)),
            sds(np.ones(k, np.float32)))
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree.map(lambda _: sh(state_spec), args[0]),
             jax.tree.map(lambda _: sh(env_spec), args[1]),
             sh(P()), sh(P("data")))
    return jax.jit(shard_d, in_shardings=in_sh) \
        .lower(*args).compile().as_text()


def block_round_hlo(prob, graph, k: int, m: int, *,
                    robust: str | None = None, mesh=None):
    """Compiled HLO of the block-mode round (K nodes on M < K devices)
    plus its ``BlockPlan``.

    ``mesh``: an M-device mesh with a ``data`` axis to compile for
    (default: one over this process's devices); a mesh of described TPU
    devices compiles without a chip. The round is built from shapes, so
    ``prob.a`` may be a ``jax.ShapeDtypeStruct``.
    """
    import dataclasses

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import topo as rtopo
    from repro.core import topology as topo
    from repro.core.cola import ColaConfig, _round_body, build_env, \
        init_state
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import (block_payload_pspec, cola_env_pspecs,
                                     cola_state_pspecs)

    if mesh is None:
        _require_devices(m)
        mesh = jax.make_mesh((m,), ("data",))
    part = make_partition(prob.n, k)
    env = jax.eval_shape(
        lambda a: build_env(dataclasses.replace(prob, a=a), part), prob.a)
    plan = rtopo.compile_block_plan(graph, m)
    cfg = ColaConfig(kappa=1.0, robust=robust)
    mix_fn, grad_mix_fn = rt._dist_mixers(
        "data", k // m, 1, "plan", cfg.gossip_steps, plan, robust=robust)
    body = _round_body(prob, part, cfg, mix_fn=mix_fn,
                      grad_mix_fn=grad_mix_fn)
    state_spec, env_spec = cola_state_pspecs("data"), cola_env_pspecs("data")
    shard_step = jax.shard_map(
        lambda st, e, pay, act: body(st, e, pay, act), mesh=mesh,
        in_specs=(state_spec, env_spec, block_payload_pspec("data"),
                  P("data")),
        out_specs=state_spec)
    w = topo.metropolis_weights(graph).astype(np.float32)
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype)
    args = (jax.eval_shape(lambda: init_state(prob, part)), env, sds(w),
            sds(np.ones(k, np.float32)))
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree.map(lambda _: sh(state_spec), args[0]),
             jax.tree.map(lambda _: sh(env_spec), args[1]),
             sh(block_payload_pspec("data")), sh(P("data")))
    hlo = jax.jit(shard_step, in_shardings=in_sh) \
        .lower(*args).compile().as_text()
    return hlo, plan


def quant_round_hlo(prob, graph, k: int, m: int, wire: str, *,
                    pipeline: bool = False,
                    inject_fp32_leak: bool = False):
    """Compiled HLO of the quantized-wire round — the block program
    ``run_dist_cola(comm="plan", wire=...)`` executes (quantized wires
    always lower through the BlockPlan, even at one node per device) —
    plus its ``BlockPlan``.

    ``pipeline=True`` lowers the double-buffered body: round t's step-0
    payload was encoded at the end of round t-1 and rides ``ColaState.buf``,
    so the first ppermutes depend only on carried state, not on this
    round's compute. ``inject_fp32_leak`` plants the seeded violation for
    the verifier selftest: the raw fp32 dual block crossing the wire that
    the codec exists to narrow — the claimed-int8 byte cap must catch it.
    """
    import jax
    import jax.numpy as jnp
    from jax import lax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro import topo as rtopo
    from repro.core import quant, topology as topo
    from repro.core.cola import (ColaConfig, _arm_wire_state, _round_body,
                                 build_env, init_state)
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import (block_payload_pspec, cola_env_pspecs,
                                     cola_state_pspecs)

    _require_devices(m)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    mesh = jax.make_mesh((m,), ("data",))
    plan = rtopo.compile_block_plan(graph, m)
    cfg = ColaConfig(kappa=1.0, wire=wire, pipeline=pipeline)
    mix_fn, grad_mix_fn = rt._dist_mixers("data", k // m, 1, "plan",
                                          cfg.gossip_steps, plan)
    qmix_fn, qencode_fn = rt._dist_qmixers("data", k // m, "plan", cfg,
                                           plan)
    body = _round_body(prob, part, cfg, mix_fn=mix_fn,
                       grad_mix_fn=grad_mix_fn, qmix_fn=qmix_fn,
                       qencode_fn=qencode_fn)

    def round_fn(st, e, pay, act, qk, qk_next):
        new = body(st, e, pay, act, None, None, qk,
                   qk_next if pipeline else None)
        if inject_fp32_leak:
            # the seeded violation: a live fp32 (K/M, d) payload ppermuted
            # around the mesh — exactly the wide wire the codec narrows
            leak = lax.ppermute(st.v_stack, "data",
                                [(i, (i + 1) % m) for i in range(m)])
            new = new._replace(
                v_stack=new.v_stack + leak * jnp.float32(1e-30))
        return new

    state = init_state(prob, part)
    keys = np.asarray(quant.round_keys(0, 2))
    state = _arm_wire_state(state, cfg, keys[0])
    state_spec, env_spec = cola_state_pspecs("data"), cola_env_pspecs("data")
    shard_step = jax.shard_map(
        round_fn, mesh=mesh,
        in_specs=(state_spec, env_spec, block_payload_pspec("data"),
                  P("data"), P(), P()),
        out_specs=state_spec)
    w = topo.metropolis_weights(graph).astype(np.float32)
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    args = (jax.tree.map(sds, state), jax.tree.map(sds, env), sds(w),
            sds(np.ones(k, np.float32)), sds(keys[0]), sds(keys[1]))
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree.map(lambda _: sh(state_spec), args[0]),
             jax.tree.map(lambda _: sh(env_spec), args[1]),
             sh(block_payload_pspec("data")), sh(P("data")),
             sh(P()), sh(P()))
    hlo = jax.jit(shard_step, in_shardings=in_sh) \
        .lower(*args).compile().as_text()
    return hlo, plan


def _param_only_chain(comp, start_ops, allowed=(
        "get-tuple-element", "bitcast", "bitcast-convert", "reshape",
        "copy", "convert", "transpose", "tuple", "constant",
        "broadcast")) -> bool:
    """True iff every transitive operand of ``start_ops`` resolves to a
    computation parameter through shape-plumbing ops only — i.e. the value
    was ready at computation entry, with no compute on the critical path."""
    by_name = {op.name: op for op in comp.ops}
    from repro.launch.hlo_analysis import _operands
    stack = [by_name[sym] for op in start_ops
             for sym in _operands(op) if sym in by_name]
    seen = set()
    while stack:
        op = stack.pop()
        if op.name in seen:
            continue
        seen.add(op.name)
        if op.opcode == "parameter":
            continue
        if op.opcode not in allowed:
            return False
        for sym in _operands(op):
            if sym in by_name:
                stack.append(by_name[sym])
    return True


def pipeline_order_findings(hlo: str, where: str) -> List[Finding]:
    """The pipelined round body must issue its first collective-permute
    from the CARRIED double buffer: the payload's operand chain reaches
    computation parameters without any compute (no quantize reduce, no CD
    dot), which is what lets the exchange overlap this round's solve. The
    unpipelined body fails this — its step-0 payload is quantized from the
    round's own v, so the permute waits on an absmax reduction."""
    from repro.launch import hlo_analysis
    comps, _ = hlo_analysis.parse_module(hlo)
    checked = 0
    for comp in comps.values():
        perms = [op for op in comp.ops
                 if op.opcode.startswith("collective-permute")
                 and not op.opcode.endswith("-done")]
        if not perms:
            continue
        checked += 1
        if _param_only_chain(comp, perms[:1]):
            return []
    if not checked:
        return [Finding("pipeline-order",
                        "no computation issues a collective-permute — the "
                        "round body lost its neighbor exchange", where=where)]
    return [Finding(
        "pipeline-order",
        "first collective-permute depends on this round's compute (its "
        "operand chain does not resolve to carried parameters) — the "
        "double-buffered payload is not overlapping the solve",
        where=where)]


def certificate_record_hlo(prob, graph, k: int, conn: int = 1,
                           comm: str = "ring") -> str:
    """Compiled HLO of the dist certificate record program (``comm`` in
    ring/plan) — the O(d)-budget program."""
    import jax
    from jax.sharding import NamedSharding
    from repro import topo as rtopo
    from repro.core import metrics as metrics_lib, topology as topo
    from repro.core.cola import build_env, init_state
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import cola_state_pspecs

    _require_devices(k)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    mesh = jax.make_mesh((k,), ("data",))
    rec = metrics_lib.make_recorder("certificate", prob, part, env, graph,
                                    topo.metropolis_weights(graph), 0.1)
    rec = rt._place_recorder(rec, mesh, "data")
    plan = rtopo.compile_plan(graph) if comm == "plan" else None
    record = rt._certificate_dist_record(rec, mesh, "data", 1, comm, conn,
                                         plan)
    state = init_state(prob, part)
    sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       state)
    sh = NamedSharding(mesh, cola_state_pspecs("data"))
    shardings = (jax.tree.map(lambda _: sh, sds),)
    return jax.jit(record, in_shardings=shardings) \
        .lower(sds).compile().as_text()


def gap_record_hlo(prob, k: int) -> str:
    """Compiled HLO of the gather-``GapRecorder`` record program — the
    contrast program that must move >= K*d bytes."""
    import jax
    from jax.sharding import NamedSharding
    from repro.core import metrics as metrics_lib
    from repro.core.cola import init_state
    from repro.core.partition import make_partition
    from repro.dist.sharding import cola_state_pspecs

    _require_devices(k)
    part = make_partition(prob.n, k)
    mesh = jax.make_mesh((k,), ("data",))
    gap = metrics_lib.GapRecorder(prob, part)
    state = init_state(prob, part)
    sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype),
                       state)
    sh = NamedSharding(mesh, cola_state_pspecs("data"))
    shardings = (jax.tree.map(lambda _: sh, sds),)
    return jax.jit(gap.record_fn, in_shardings=shardings) \
        .lower(sds).compile().as_text()


# -- the simulator round block (jaxpr passes + donation) --------------------

def _sim_block_program(cfg):
    import jax.numpy as jnp
    from jax import lax
    from repro.core import topology as topo
    from repro.core.cola import _round_body, build_env, init_state
    from repro.core.partition import make_partition

    prob = _ridge()
    k, t = 8, 4
    graph = topo.ring(k)
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    state = init_state(prob, part)
    body = _round_body(prob, part, cfg)
    w = topo.metropolis_weights(graph).astype(np.float32)

    def block(st, ctx, sched):
        def step(s, xs):
            return body(s, ctx, xs["w"], xs["active"]), None
        return lax.scan(step, st, sched)[0]

    sched = {"w": jnp.stack([jnp.asarray(w)] * t),
             "active": jnp.ones((t, k), jnp.float32)}
    return block, (state, env, sched)


def sim_block_compiled(prob, graph, cfg, block_size: int, *, device):
    """Compile, from shapes, the block program ``run_cola`` dispatches on a
    static graph with the gap recorder: ``executor.block_program`` over
    ``block_size`` rounds, the state donated, the env and the recorder's
    arrays (the data matrix first) as arguments. ``device``: where to
    compile; a described TPU device compiles without a chip. ``prob.a``
    may be a ``jax.ShapeDtypeStruct``.
    """
    import dataclasses

    import jax
    from jax.sharding import SingleDeviceSharding
    from repro.core import executor, metrics as metrics_lib
    from repro.core.cola import _round_body, build_env, init_state
    from repro.core.partition import make_partition

    k = graph.num_nodes
    part = make_partition(prob.n, k)
    body = _round_body(prob, part, cfg)

    def step_fn(s, env, sched_t):
        return body(s, env, sched_t["w"], sched_t["active"]), None

    def on_device(tree):
        sharding = SingleDeviceSharding(device)
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=sharding), tree)

    with_a = lambda f: lambda a: f(dataclasses.replace(prob, a=a))
    a_sds = jax.ShapeDtypeStruct(prob.a.shape, prob.a.dtype)
    env = jax.eval_shape(with_a(lambda p: build_env(
        p, part, with_gram=cfg.use_gram(p.d, part.block,
                                        p.a.dtype.itemsize))), a_sds)
    state = jax.eval_shape(with_a(lambda p: init_state(p, part)), a_sds)
    sched = {"w": jax.ShapeDtypeStruct((block_size, k, k), np.float32),
             "active": jax.ShapeDtypeStruct((block_size, k), np.float32)}
    sched_t = {n: jax.ShapeDtypeStruct(x.shape[1:], x.dtype)
               for n, x in sched.items()}
    lifted_rec, rec_consts = executor.lift_constants(
        lambda a, s, _t: metrics_lib.GapRecorder(
            dataclasses.replace(prob, a=a), part).record_fn(s),
        state, sched_t, data=(a_sds,))
    program = executor.block_program(step_fn, lifted_rec)
    args = on_device((state, (env, rec_consts), sched,
                      jax.ShapeDtypeStruct((block_size,), np.bool_)))
    return program.lower(*args).compile()


def _check_sim(cfg, name: str) -> List[Finding]:
    block, args = _sim_block_program(cfg)
    findings = passes.run_jaxpr_passes(block, *args, where=name)
    findings += passes.donation(block, args, (0,), where=name)
    return findings


def _check_comm_to_findings(check: Callable[[], dict],
                            name: str) -> List[Finding]:
    try:
        check()
    except contracts.CommContractViolation as e:
        return [Finding("comm-contract", str(e), where=name)]
    return []


@register_driver("sim")
def check_sim() -> List[Finding]:
    from repro.core.cola import ColaConfig
    return _check_sim(ColaConfig(kappa=1.0), "sim")


@register_driver("sim-robust")
def check_sim_robust() -> List[Finding]:
    from repro.core.cola import ColaConfig
    return _check_sim(ColaConfig(kappa=1.0, robust="trim"), "sim-robust")


@register_driver("gossip-dp")
def check_gossip_dp() -> List[Finding]:
    import jax
    import jax.numpy as jnp
    from jax import lax
    from repro.core import topology as topo
    from repro.optim import gossip as gossip_lib
    from repro.optim.privacy import DPConfig

    k = 8
    gcfg = gossip_lib.GossipConfig(num_nodes=k)
    dp = DPConfig(clip=1.0, sigma=1.0)
    mixer = gossip_lib._param_mixer(gcfg, None, None, None, dp)
    w = jnp.asarray(topo.metropolis_weights(gcfg.graph()),
                    dtype=jnp.float32)
    params = {"w": jnp.zeros((k, 16), jnp.float32),
              "b": jnp.zeros((k,), jnp.float32)}
    key = jax.random.PRNGKey(0)

    def prog(p, w_, key_):
        def step(pp, i):
            return mixer(w_, pp, jax.random.fold_in(key_, i)), None
        return lax.scan(step, p, jnp.arange(4))[0]

    return passes.run_jaxpr_passes(prog, params, w, key, where="gossip-dp")


@register_driver("dist-ring")
def check_dist_ring() -> List[Finding]:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.core import topology as topo
    from repro.core.cola import ColaConfig, _round_body, build_env, \
        init_state
    from repro.core.partition import make_partition
    from repro.dist import runtime as rt
    from repro.dist.sharding import cola_env_pspecs, cola_state_pspecs

    k, conn = 4, 1
    _require_devices(k)
    prob = _ridge()
    part = make_partition(prob.n, k)
    env = build_env(prob, part)
    mesh = jax.make_mesh((k,), ("data",))
    cfg = ColaConfig(kappa=1.0)
    mix_fn, grad_mix_fn = rt._dist_mixers("data", 1, conn, "ring",
                                          cfg.gossip_steps)
    body = _round_body(prob, part, cfg, mix_fn=mix_fn,
                      grad_mix_fn=grad_mix_fn)
    state_spec, env_spec = cola_state_pspecs("data"), cola_env_pspecs("data")
    shard_step = jax.shard_map(
        lambda st, e, w_, act: body(st, e, w_, act), mesh=mesh,
        in_specs=(state_spec, env_spec, P(), P("data")),
        out_specs=state_spec)
    w = topo.metropolis_weights(topo.ring(k))
    sds = lambda a: jax.ShapeDtypeStruct(np.shape(a), np.asarray(a).dtype)
    args = (jax.tree.map(sds, init_state(prob, part)),
            jax.tree.map(sds, env), sds(w.astype(np.float32)),
            sds(np.ones(k, np.float32)))
    sh = lambda spec: NamedSharding(mesh, spec)
    in_sh = (jax.tree.map(lambda _: sh(state_spec), args[0]),
             jax.tree.map(lambda _: sh(env_spec), args[1]),
             sh(P()), sh(P("data")))
    hlo = jax.jit(shard_step, in_shardings=in_sh) \
        .lower(*args).compile().as_text()
    return _check_comm_to_findings(
        lambda: contracts.check_comm(
            hlo, contracts.ring_contract(prob.d, conn)), "dist-ring")


@register_driver("dist-plan")
def check_dist_plan() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso()
    k = 4
    hlo, plan = plan_round_hlo(prob, topo.torus_2d(2, k // 2), k)
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, plan.contract(prob.d)),
        "dist-plan")


@register_driver("dist-plan-sampled")
def check_dist_plan_sampled() -> List[Finding]:
    """Client sampling on the compiled per-node plan: the complete-graph
    round program honors the full plan's comm contract, and every sampled
    cohort's churn-reweighted W stays executable on that SAME static plan
    — its (diag, coefs) lowering round-trips through
    ``w_from_coefficients`` exactly (sampling only zeroes edges, never
    grows support), which is what keeps one compiled program valid across
    a streamed participation schedule."""
    from repro import topo as rtopo
    from repro.core import schedule as schedule_lib, topology as topo

    prob = _lasso()
    k = 4
    graph = topo.complete(k)
    hlo, plan = plan_round_hlo(prob, graph, k)
    findings = _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, plan.contract(prob.d)),
        "dist-plan-sampled")
    sample = schedule_lib.SampleConfig(k_active=2, mode="dense")
    mask_fn = schedule_lib.participation_callable(k, sample, run_seed=0)
    rng = np.random.default_rng(0)
    for t in range(8):
        mask = mask_fn(t, rng)
        w_t = np.asarray(topo.reweight_for_active(graph, mask))
        try:
            diag, coefs = rtopo.plan_coefficients(plan, w_t, check=True)
        except ValueError as e:
            findings.append(Finding(
                "comm-contract",
                f"round {t} sampled mask {mask.astype(int).tolist()} "
                f"reweights outside the compiled complete-graph plan: {e}",
                where="dist-plan-sampled"))
            continue
        if not (rtopo.w_from_coefficients(plan, diag, coefs) == w_t).all():
            findings.append(Finding(
                "comm-contract",
                f"round {t} (diag, coefs) lowering does not round-trip to "
                "the sampled W — the plan would execute a different matrix "
                "than the certificate accounts for",
                where="dist-plan-sampled"))
    return findings


@register_driver("dist-dense")
def check_dist_dense() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso()
    k, itemsize = 4, 4
    hlo = dense_round_hlo(prob, topo.torus_2d(2, k // 2), k)
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, contracts.gather_contract(
            "dense-oracle", min_all_gather_bytes=prob.d * itemsize)),
        "dist-dense")


@register_driver("dist-block")
def check_dist_block() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso(153, 48)
    k, m = 9, 3
    hlo, plan = block_round_hlo(prob, topo.complete(k), k, m)
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, plan.contract(prob.d)),
        "dist-block")


@register_driver("dist-block-robust")
def check_dist_block_robust() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso()
    k, m = 8, 4
    hlo, plan = block_round_hlo(prob, topo.torus_2d(2, 4), k, m,
                                robust="trim")
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, plan.contract(prob.d)),
        "dist-block-robust")


@register_driver("dist-plan-int8")
def check_dist_plan_int8() -> List[Finding]:
    """The quantized wire's headline contract: the int8 round program
    (what ``run_dist_cola(comm="plan", wire="int8")`` compiles) moves at
    most the narrow-wire ppermute budget — itself required to be <= 0.3x
    the fp32 budget — and gathers nothing."""
    from repro.core import topology as topo
    prob = _lasso()
    k, m = 8, 4
    hlo, plan = quant_round_hlo(prob, topo.torus_2d(2, 4), k, m, "int8")
    contract = plan.contract(prob.d, wire="int8")
    fp32_cap = plan.contract(prob.d).max_collective_permute_bytes
    findings = []
    if contract.max_collective_permute_bytes > 0.3 * fp32_cap:
        findings.append(Finding(
            "comm-contract",
            f"int8 wire budget {contract.max_collective_permute_bytes:,.0f}"
            f" B/device exceeds 0.3x the fp32 budget {fp32_cap:,.0f} — the"
            " codec is not actually narrowing the wire",
            where="dist-plan-int8"))
    return findings + _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, contract), "dist-plan-int8")


@register_driver("dist-plan-fp8-pipelined")
def check_dist_plan_fp8_pipelined() -> List[Finding]:
    """The double-buffered fp8 round: same narrow-wire comm contract, plus
    the pipeline-structure check — the first ppermute must consume the
    CARRIED payload buffer (no compute on its operand chain), which is the
    HLO-visible form of 'comm overlaps the CD solve'."""
    from repro.core import topology as topo
    prob = _lasso()
    k, m = 8, 4
    hlo, plan = quant_round_hlo(prob, topo.torus_2d(2, 4), k, m, "fp8",
                                pipeline=True)
    findings = _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, plan.contract(prob.d, wire="fp8")),
        "dist-plan-fp8-pipelined")
    return findings + pipeline_order_findings(hlo, "dist-plan-fp8-pipelined")


@register_driver("cert-ring")
def check_cert_ring() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso()
    k, conn = 4, 1
    hlo = certificate_record_hlo(prob, topo.ring(k), k, conn, comm="ring")
    return _check_comm_to_findings(
        lambda: contracts.check_comm(
            hlo, contracts.certificate_contract(prob.d, conn)), "cert-ring")


@register_driver("cert-plan")
def check_cert_plan() -> List[Finding]:
    from repro.core import topology as topo
    prob = _lasso()
    k, itemsize = 4, 4
    graph = topo.torus_2d(2, k // 2)
    hlo = certificate_record_hlo(prob, graph, k, 1, comm="plan")
    # plan-path certificate: one (d,) ppermute per color + the O(d) psum
    from repro import topo as rtopo
    plan = rtopo.compile_plan(graph)
    contract = contracts.CommContract(
        name=f"certificate-plan-c{plan.num_colors}-d{prob.d}",
        forbid=("all-gather", "reduce-scatter", "all-to-all"),
        max_collective_permute_bytes=plan.num_colors * prob.d * itemsize,
        max_all_reduce_bytes=(4 * prob.d + 64) * itemsize)
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, contract), "cert-plan")


@register_driver("gap-record")
def check_gap_record() -> List[Finding]:
    prob = _lasso()
    k, itemsize = 4, 4
    hlo = gap_record_hlo(prob, k)
    return _check_comm_to_findings(
        lambda: contracts.check_comm(hlo, contracts.gather_contract(
            "gap-recorder", min_total_bytes=k * prob.d * itemsize)),
        "gap-record")


@register_driver("executor-retrace")
def check_executor_retrace() -> List[Finding]:
    """The block executor must resolve a repeated identical run as cache
    hits (content-addressed keys): any miss on the warmed run is a
    retrace."""
    from repro.core import executor
    from repro.core.cola import ColaConfig, run_cola
    from repro.core import topology as topo

    prob = _ridge()
    cfg = ColaConfig(kappa=1.0)
    graph = topo.ring(8)

    def run():
        run_cola(prob, graph, cfg, 4, record_every=2, executor="block",
                 block_size=2)

    return passes.check_retrace(run, where="executor-retrace")
