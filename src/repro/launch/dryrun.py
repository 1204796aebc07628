"""Multi-pod dry-run: AOT-lower + compile every (arch x shape x mesh) step.

This is how the distribution config is proven coherent without hardware: the
production mesh (16x16 single pod / 2x16x16 multi-pod) is built from 512
placeholder CPU devices, every step is lowered with ShapeDtypeStruct inputs
(no allocation), compiled, and its memory/cost analysis + collective schedule
recorded for the roofline analysis (EXPERIMENTS.md).

Usage:
  PYTHONPATH=src python -m repro.launch.dryrun --arch qwen3-4b --shape train_4k
  PYTHONPATH=src python -m repro.launch.dryrun --all [--multi-pod] [--out DIR]
"""
# The first two lines MUST run before any other import (jax locks the device
# count on first init).
import os
os.environ["XLA_FLAGS"] = ("--xla_force_host_platform_device_count=512 "
                           + os.environ.get("XLA_FLAGS", ""))

import argparse
import dataclasses
import json
import re
import time
from typing import Any

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from repro.configs.base import ARCHS, SHAPES, InputShape, ModelConfig, \
    get_config
from repro.dist import sharding as shd
from repro.launch import mesh as mesh_lib
from repro.launch import specs as specs_lib
from repro.models import transformer
from repro.models.blocks import ModelCtx
from repro.launch import hlo_analysis
from repro.train.steps import (TrainHParams, make_decode_step,
                               make_prefill_step, make_train_step)

_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter", "all-to-all",
                "collective-permute")

# dims like bf16[16,1024,8]{...}
_SHAPE_RE = re.compile(
    r"(f64|f32|f16|bf16|s64|s32|s16|s8|u64|u32|u16|u8|pred)\[([\d,]*)\]")
_DTYPE_BYTES = {"f64": 8, "f32": 4, "f16": 2, "bf16": 2, "s64": 8, "s32": 4,
                "s16": 2, "s8": 1, "u64": 8, "u32": 4, "u16": 2, "u8": 1,
                "pred": 1}


def _shape_bytes(text: str) -> int:
    """Sum the sizes of all array shapes in an HLO result-type string."""
    total = 0
    for m in _SHAPE_RE.finditer(text):
        dtype, dims = m.groups()
        n = 1
        for d in dims.split(","):
            if d:
                n *= int(d)
        total += n * _DTYPE_BYTES[dtype]
    return total


def collective_bytes(hlo_text: str) -> dict:
    """Per-collective-kind bytes from the SPMD-partitioned HLO.

    Shapes in the partitioned module are per-device, so the sums are
    per-device bytes moved (all-reduce counted twice for the reduce+broadcast
    ring phases). ``-start`` variants cover the async forms.
    """
    out = {k: 0 for k in _COLLECTIVES}
    for line in hlo_text.splitlines():
        ls = line.lstrip()
        # result-typed op lines look like: %name = TYPE op-name(...)
        m = re.match(r"%?[\w.\-]+ = (.+?) ([\w\-]+)\(", ls)
        if not m:
            continue
        result_type, op = m.groups()
        base = op.removesuffix("-start")
        if base in _COLLECTIVES:
            factor = 2 if base == "all-reduce" else 1
            out[base] += factor * _shape_bytes(result_type)
    out["total"] = sum(out[k] for k in _COLLECTIVES)
    return out


def _named(tree: Any, mesh, spec_tree: Any):
    return jax.tree.map(lambda s: NamedSharding(mesh, s), spec_tree,
                        is_leaf=lambda x: isinstance(x, P))


@dataclasses.dataclass(frozen=True)
class Opts:
    """Perf-hillclimb knobs (EXPERIMENTS.md §Perf). Defaults = baseline."""

    attn_bf16: bool = False        # bf16 score/PV operands (f32 accum)
    remat_policy: str = "full"     # full | dots | none
    microbatches: int = 1
    act_constraint: bool = False   # pin layer-boundary activation sharding
    param_dtype: str | None = None  # e.g. "bfloat16" master params
    state_dtype: str = "float32"   # optimizer moment dtype
    gossip_pod: bool = False       # CoLA gossip-DP across pods (train only)
    moe_grouped: bool = False      # token-grouped MoE dispatch
    serve_resident: bool = False   # serving: no FSDP — weights stay resident
    #   (model-sharded only); kills the per-token weight all-gather
    swa_window: int = 0            # >0: force sliding-window attention —
    #   gives quadratic-attention archs a sub-quadratic long_500k variant

    def apply_cfg(self, cfg: ModelConfig) -> ModelConfig:
        updates = {}
        if self.attn_bf16:
            updates["attn_compute_dtype"] = "bfloat16"
        if self.remat_policy != "full":
            updates["remat_policy"] = self.remat_policy
        if self.param_dtype:
            updates["param_dtype"] = self.param_dtype
        if self.swa_window and cfg.attention == "full":
            updates["attention"] = "sliding"
            updates["window"] = self.swa_window
        return dataclasses.replace(cfg, **updates) if updates else cfg

    def tag(self) -> str:
        bits = []
        if self.attn_bf16: bits.append("attnbf16")
        if self.remat_policy != "full": bits.append(f"remat-{self.remat_policy}")
        if self.microbatches > 1: bits.append(f"mb{self.microbatches}")
        if self.act_constraint: bits.append("actspec")
        if self.param_dtype: bits.append(f"p-{self.param_dtype}")
        if self.state_dtype != "float32": bits.append(f"s-{self.state_dtype}")
        if self.gossip_pod: bits.append("gossip")
        if self.moe_grouped: bits.append("moegrp")
        if self.serve_resident: bits.append("resident")
        if self.swa_window: bits.append(f"swa{self.swa_window}")
        return "+".join(bits) or "baseline"


BASELINE = Opts()


def model_ctx(mesh) -> ModelCtx:
    # MoE dispatch runs in global scatter mode and lets GSPMD partition the
    # per-expert einsums over the ``model`` axis (expert weights are sharded
    # by param_pspecs). A manual shard_map under remat+scan trips an XLA
    # SPMD bug ("Invalid binary instruction opcode copy"), so the manual
    # expert-parallel path is reserved for the executed (non-AOT) runtime.
    return ModelCtx(mesh=None, model_axis=None, moe_mode="scatter")


def model_ctx_opt(mesh, axes, opts: Opts) -> ModelCtx:
    groups = 1
    if opts.moe_grouped:
        sizes = _mesh_sizes(mesh)
        groups = sizes[axes.data] * (sizes.get("pod", 1)
                                     if axes.pod else 1)
    if not opts.act_constraint and groups <= 1:
        return model_ctx(mesh)
    return ModelCtx(mesh=mesh if opts.act_constraint else None,
                    model_axis=None, moe_mode="scatter",
                    act_spec=(P(axes.batch_axes, None, None)
                              if opts.act_constraint else None),
                    dispatch_groups=groups)


def _mesh_sizes(mesh) -> dict:
    return dict(zip(mesh.axis_names, mesh.devices.shape))


def _param_flags(kind: str, opts: Opts) -> dict:
    """param_pspecs kwargs per shape kind — the ONE mapping shared by the
    artifact builders and render_plan so the printed plan always matches
    the lowered in_shardings: training always runs FSDP (+ expert grouping
    with --moe-grouped); serving may keep weights resident (model-sharded
    only, --serve-resident) and never groups experts."""
    if kind == "train":
        return dict(fsdp=True, moe_output_fsdp=opts.moe_grouped)
    return dict(fsdp=not opts.serve_resident, moe_output_fsdp=False)


def _train_artifacts(cfg: ModelConfig, shape: InputShape, mesh, axes,
                     hp: TrainHParams, opts: Opts = BASELINE):
    state_sds = specs_lib.state_specs(cfg, hp)
    batch_sds = specs_lib.input_specs(cfg, shape)
    pspecs = shd.param_pspecs(
        state_sds.params, axes, _mesh_sizes(mesh),
        **_param_flags("train", opts))
    # opt_state is {"m": params-like, "v": params-like}
    state_specs_tree = state_sds._replace(
        params=pspecs, opt_state={"m": pspecs, "v": pspecs}, step=P())
    batch_specs_tree = shd.batch_pspecs(cfg, shape, axes)
    step_fn = make_train_step(cfg, hp, model_ctx_opt(mesh, axes, opts))
    in_shardings = (_named(state_sds, mesh, state_specs_tree),
                    _named(batch_sds, mesh, batch_specs_tree))
    out_shardings = (_named(state_sds, mesh, state_specs_tree),
                     None)
    fn = jax.jit(step_fn, in_shardings=in_shardings,
                 out_shardings=out_shardings)
    return fn, (state_sds, batch_sds)


def _prefill_artifacts(cfg: ModelConfig, shape: InputShape, mesh, axes,
                       opts: Opts = BASELINE):
    params_sds = specs_lib.params_specs(cfg)
    batch_sds = specs_lib.input_specs(cfg, shape)
    cache_sds = specs_lib.cache_specs(cfg, shape.global_batch, shape.seq_len)
    pspecs = shd.param_pspecs(
        params_sds, axes, _mesh_sizes(mesh), **_param_flags("prefill", opts))
    bspecs = shd.batch_pspecs(cfg, shape, axes)
    cspecs = shd.cache_pspecs(
        cfg, cache_sds, shape.global_batch, axes, _mesh_sizes(mesh))
    step_fn = make_prefill_step(cfg, model_ctx_opt(mesh, axes, opts))
    fn = jax.jit(step_fn, in_shardings=(
        _named(params_sds, mesh, pspecs), _named(batch_sds, mesh, bspecs),
        _named(cache_sds, mesh, cspecs)))
    return fn, (params_sds, batch_sds, cache_sds)


def _decode_artifacts(cfg: ModelConfig, shape: InputShape, mesh, axes,
                      opts: Opts = BASELINE):
    b = shape.global_batch
    params_sds = specs_lib.params_specs(cfg)
    cache_sds = specs_lib.cache_specs(cfg, b, shape.seq_len)
    pspecs = shd.param_pspecs(
        params_sds, axes, _mesh_sizes(mesh), **_param_flags("decode", opts))
    cspecs = shd.cache_pspecs(
        cfg, cache_sds, b, axes, _mesh_sizes(mesh))
    tok_sds = jax.ShapeDtypeStruct((b, 1), jnp.int32)
    t_sds = jax.ShapeDtypeStruct((), jnp.int32)
    batch_ax = axes.batch_axes if b >= 16 else ()
    tok_spec = P(batch_ax, None) if batch_ax else P()
    step_fn = make_decode_step(cfg, model_ctx_opt(mesh, axes, opts))
    args = [params_sds, tok_sds, t_sds, cache_sds]
    in_sh = [_named(params_sds, mesh, pspecs),
             NamedSharding(mesh, tok_spec), NamedSharding(mesh, P()),
             _named(cache_sds, mesh, cspecs)]
    kwargs = {}
    if cfg.family == "encdec":
        # cross-attention KV computed once at request admission
        enc_sds = jax.ShapeDtypeStruct(
            (b, shape.seq_len, cfg.frontend_dim), jnp.bfloat16)
        enc_kv_sds = jax.eval_shape(
            lambda p, e: transformer._enc_kv_all_layers(
                cfg, p, transformer.encode(cfg, p, e)[0]),
            params_sds, enc_sds)
        enc_pos_sds = jax.ShapeDtypeStruct((b, shape.seq_len), jnp.int32)
        kv_spec = jax.tree.map(lambda _: NamedSharding(
            mesh, P(None, axes.data if b >= 16 else None, None, None, None)),
            enc_kv_sds)
        kwargs = {"enc_kv": enc_kv_sds, "enc_pos": enc_pos_sds}
        fn = jax.jit(lambda p, tok, t, c, enc_kv, enc_pos: step_fn(
            p, tok, t, c, enc_kv=enc_kv, enc_pos=enc_pos),
            in_shardings=tuple(in_sh) + (
                kv_spec, NamedSharding(
                    mesh, P(axes.batch_axes if b >= 16 else None, None))))
        return fn, tuple(args) + (enc_kv_sds, enc_pos_sds)
    fn = jax.jit(step_fn, in_shardings=tuple(in_sh))
    return fn, tuple(args)


def _gossip_train_artifacts(cfg: ModelConfig, shape: InputShape, mesh, axes,
                            hp: TrainHParams, opts: Opts):
    """CoLA gossip-DP across pods: each pod holds its own replica (sharded
    over data/model within the pod), takes a local step on its own batch
    shard, then parameter-mixes with its neighbor pod via collective-permute
    — the cross-pod gradient all-reduce disappears from the program."""
    from jax import lax

    n_pods = _mesh_sizes(mesh)["pod"]
    state_sds = specs_lib.state_specs(cfg, hp)
    stacked_sds = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_pods,) + l.shape, l.dtype),
        state_sds)
    b_local = shape.global_batch // n_pods
    batch_one = specs_lib.input_specs(cfg, shape)
    stacked_batch = jax.tree.map(
        lambda l: jax.ShapeDtypeStruct((n_pods, b_local) + l.shape[1:],
                                       l.dtype), batch_one)

    pod_axes = shd.MeshAxes()  # within-pod layout (data, model)
    pspecs = shd.param_pspecs(
        state_sds.params, pod_axes, _mesh_sizes(mesh))
    prepend = lambda spec: P("pod", *tuple(spec))
    pod_pspecs = jax.tree.map(prepend, pspecs,
                              is_leaf=lambda x: isinstance(x, P))
    state_specs_tree = state_sds._replace(
        params=pod_pspecs, opt_state={"m": pod_pspecs, "v": pod_pspecs},
        step=P())
    bspec_one = shd.batch_pspecs(cfg, shape, pod_axes)
    bspecs = jax.tree.map(prepend, bspec_one,
                          is_leaf=lambda x: isinstance(x, P))

    local_step = make_train_step(cfg, hp, model_ctx_opt(mesh, pod_axes, opts))

    def mix_params(params_stacked):
        def mix_leaf(p_local):
            # p_local: (1, ...) this pod's replica; pairwise Metropolis mix
            other = lax.ppermute(p_local, "pod",
                                 [(i, (i + 1) % n_pods) for i in range(n_pods)])
            return (0.5 * p_local.astype(jnp.float32)
                    + 0.5 * other.astype(jnp.float32)).astype(p_local.dtype)
        return jax.tree.map(mix_leaf, params_stacked)

    shard_mix = jax.shard_map(mix_params, mesh=mesh, in_specs=P("pod"),
                              out_specs=P("pod"))

    def gossip_step(states, batches):
        new_states, metrics = jax.vmap(local_step)(states, batches)
        mixed = shard_mix(new_states.params)
        return new_states._replace(params=mixed), metrics

    fn = jax.jit(gossip_step,
                 in_shardings=(_named(stacked_sds, mesh, state_specs_tree),
                               _named(stacked_batch, mesh, bspecs)),
                 out_shardings=(_named(stacked_sds, mesh, state_specs_tree),
                                None))
    return fn, (stacked_sds, stacked_batch)


def lower_pair(arch: str, shape_name: str, *, multi_pod: bool = False,
               hp: TrainHParams | None = None, compile_: bool = True,
               opts: Opts = BASELINE) -> dict:
    """Lower + compile one (arch, shape, mesh) combination; return the report."""
    cfg = opts.apply_cfg(get_config(arch))
    shape = SHAPES[shape_name]
    if _shape_infeasible(cfg, shape_name):
        return {"arch": arch, "shape": shape_name,
                "mesh": "multi_pod" if multi_pod else "single_pod",
                "status": "skipped",
                "reason": "full quadratic attention; see DESIGN.md"}
    hp = hp or TrainHParams(microbatches=opts.microbatches,
                            state_dtype=opts.state_dtype)
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    axes = mesh_lib.mesh_axes(multi_pod)
    t0 = time.time()
    with jax.default_device(jax.devices("cpu")[0]):
        if shape.kind == "train":
            if opts.gossip_pod:
                assert multi_pod, "--gossip-pod needs the multi-pod mesh"
                fn, args = _gossip_train_artifacts(cfg, shape, mesh, axes,
                                                   hp, opts)
            else:
                fn, args = _train_artifacts(cfg, shape, mesh, axes, hp, opts)
        elif shape.kind == "prefill":
            fn, args = _prefill_artifacts(cfg, shape, mesh, axes, opts)
        else:
            fn, args = _decode_artifacts(cfg, shape, mesh, axes, opts)
        lowered = fn.lower(*args)
    t_lower = time.time() - t0
    report = {
        "arch": arch, "shape": shape_name,
        "mesh": "multi_pod" if multi_pod else "single_pod",
        "kind": shape.kind, "status": "lowered",
        "chips": int(mesh.devices.size),
        "opts": opts.tag(),
        "lower_s": round(t_lower, 2),
    }
    if not compile_:
        return report
    t0 = time.time()
    compiled = lowered.compile()
    report["compile_s"] = round(time.time() - t0, 2)
    report["status"] = "compiled"
    cost = compiled.cost_analysis() or {}
    report["flops_per_device"] = float(cost.get("flops", 0.0))
    report["bytes_per_device"] = float(cost.get("bytes accessed", 0.0))
    mem = compiled.memory_analysis()
    if mem is not None:
        report["memory"] = {
            "argument_bytes": int(getattr(mem, "argument_size_in_bytes", 0)),
            "output_bytes": int(getattr(mem, "output_size_in_bytes", 0)),
            "temp_bytes": int(getattr(mem, "temp_size_in_bytes", 0)),
            "alias_bytes": int(getattr(mem, "alias_size_in_bytes", 0)),
        }
    hlo_text = compiled.as_text()
    # XLA's cost_analysis counts while bodies once (ignores trip counts); the
    # trip-count-aware analyzer is the authoritative roofline source.
    report["hlo"] = hlo_analysis.analyze(
        hlo_text, pod_size=256 if multi_pod else None)
    report["collectives"] = collective_bytes(hlo_text)
    return report


def _shape_infeasible(cfg: ModelConfig, shape_name: str) -> bool:
    """long_500k decode needs bounded state (see DESIGN.md) — the one
    (arch, shape) combination the sweep and the plan both skip."""
    return shape_name == "long_500k" and not cfg.sub_quadratic


def render_plan(arch: str, shape_name: str, *, multi_pod: bool = False,
                opts: Opts = BASELINE) -> str:
    """Human-readable sharding plan: every state leaf with its shape and the
    PartitionSpec the shipped rules assign it (no lowering, no allocation)."""
    cfg = opts.apply_cfg(get_config(arch))
    shape = SHAPES[shape_name]
    mesh = mesh_lib.make_production_mesh(multi_pod=multi_pod)
    axes = mesh_lib.mesh_axes(multi_pod)
    sizes = _mesh_sizes(mesh)

    lines = [f"# sharding plan: {arch} x {shape_name} on "
             f"{'x'.join(str(s) for s in mesh.devices.shape)} "
             f"({', '.join(mesh.axis_names)})"]

    def section(title, shapes_tree, specs_tree):
        lines.append(f"[{title}]")
        flat_s = jax.tree_util.tree_leaves_with_path(shapes_tree)
        flat_p = jax.tree.leaves(specs_tree,
                                 is_leaf=lambda x: isinstance(x, P))
        for (path, leaf), spec in zip(flat_s, flat_p):
            name = jax.tree_util.keystr(path)
            lines.append(f"  {name:<60} {str(leaf.shape):<24} {spec}")

    params_sds = specs_lib.params_specs(cfg)
    section("params", params_sds,
            shd.param_pspecs(params_sds, axes, sizes,
                             **_param_flags(shape.kind, opts)))
    section("batch", specs_lib.input_specs(cfg, shape),
            shd.batch_pspecs(cfg, shape, axes))
    # prefill steps shard a cache too (_prefill_artifacts) — render it for
    # every cache-carrying kind, not just decode
    if shape.kind in ("prefill", "decode") and not _shape_infeasible(
            cfg, shape_name):
        cache_sds = specs_lib.cache_specs(cfg, shape.global_batch,
                                          shape.seq_len)
        section("cache", cache_sds,
                shd.cache_pspecs(cfg, cache_sds, shape.global_batch, axes,
                                 sizes))
    return "\n".join(lines)


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default=None)
    ap.add_argument("--shape", default=None)
    ap.add_argument("--all", action="store_true")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--no-compile", action="store_true")
    ap.add_argument("--plan", action="store_true",
                    help="print the sharding plan (specs per leaf) instead "
                         "of lowering")
    ap.add_argument("--out", default="experiments/dryrun")
    ap.add_argument("--force", action="store_true",
                    help="re-run pairs whose report file already exists")
    ap.add_argument("--attn-bf16", action="store_true")
    ap.add_argument("--remat-policy", default="full",
                    choices=["full", "dots", "none"])
    ap.add_argument("--microbatches", type=int, default=1)
    ap.add_argument("--act-constraint", action="store_true")
    ap.add_argument("--param-dtype", default=None)
    ap.add_argument("--state-dtype", default="float32")
    ap.add_argument("--gossip-pod", action="store_true")
    ap.add_argument("--moe-grouped", action="store_true")
    ap.add_argument("--serve-resident", action="store_true")
    ap.add_argument("--swa-window", type=int, default=0)
    ap.add_argument("--cola-d", type=int, default=1 << 20,
                    help="--plan: CoLA problem dimension d for the recorder "
                         "collective-footprint section")
    ap.add_argument("--cola-n", type=int, default=1 << 24,
                    help="--plan: CoLA coordinate count n (n_k = n / K)")
    ap.add_argument("--cola-k", type=int, default=16,
                    help="--plan: node count for the topology-program "
                         "section (the gossip graph compiled to ppermutes)")
    ap.add_argument("--cola-m", type=int, default=None,
                    help="--plan: ALSO render each topology's block plan "
                         "for K nodes quotiented onto M < K devices "
                         "(block-level colors, per-link block bytes, "
                         "intra- vs inter-block edge split)")
    ap.add_argument("--wire", default=None,
                    choices=["fp32", "fp8", "fp8_e5m2", "int8"],
                    help="--plan: gossip wire codec — renders each "
                         "topology's byte budget (and enforced contract "
                         "line) for the quantized payload + fp32 scale "
                         "sidecar instead of the fp32 wire")
    ap.add_argument("--active", type=int, default=None,
                    help="--plan: ALSO render the streamed participation "
                         "schedule footprint for K'=ACTIVE of --cola-k "
                         "sampled nodes per round "
                         "(ColaConfig(participation=SampleConfig(...)))")
    ap.add_argument("--rounds", type=int, default=1000,
                    help="--plan --active: round count T the streamed-vs-"
                         "stacked schedule byte comparison assumes")
    ap.add_argument("--topo", default="ring,torus2d,expander,complete",
                    help="--plan: comma-separated topology names "
                         "(repro.topo.GRAPHS) whose compiled comm plans to "
                         "render; 'none' skips the section")
    args = ap.parse_args()
    if args.cola_m is not None and (
            args.cola_m < 1 or args.cola_k % args.cola_m != 0):
        ap.error(f"--cola-m {args.cola_m} must divide --cola-k "
                 f"{args.cola_k} (contiguous node blocks per device)")
    opts = Opts(attn_bf16=args.attn_bf16, remat_policy=args.remat_policy,
                microbatches=args.microbatches,
                act_constraint=args.act_constraint,
                param_dtype=args.param_dtype, state_dtype=args.state_dtype,
                gossip_pod=args.gossip_pod, moe_grouped=args.moe_grouped,
                serve_resident=args.serve_resident,
                swa_window=args.swa_window)

    pairs = []
    archs = ARCHS if args.all or args.arch is None else [
        args.arch.replace("-", "_")]
    shapes = list(SHAPES) if args.all or args.shape is None else [args.shape]
    for a in archs:
        for s in shapes:
            pairs.append((a, s))

    if args.plan:
        for a, s in pairs:
            print(render_plan(a, s, multi_pod=args.multi_pod, opts=opts),
                  flush=True)
        # the CoLA control plane rides the same meshes: show what one metric
        # record round moves per device under each recorder (the gap
        # recorder gathers the stacks; the Prop.-1 certificate recorder is
        # O(d) on the ring) so the recording cadence can be budgeted like
        # any other collective
        from repro.core import metrics as cola_metrics
        k_nodes = 2 * 256 if args.multi_pod else 16
        print(cola_metrics.render_footprints(k=k_nodes, d=args.cola_d,
                                             n_k=args.cola_n // k_nodes),
              flush=True)
        # the telemetry counter carry (ColaConfig(telemetry=True)) rides the
        # same round scan: a handful of replicated scalars plus one
        # node-sharded gate vector — budget it next to the recorders
        from repro.obs import counters as obs_counters
        print(obs_counters.render_footprint(k_nodes), flush=True)
        # streamed participation schedules (client sampling): per-round
        # schedule bytes resident inside the scan vs the (T, ...) stacks
        # streaming replaces — the million-node population budget
        if args.active is not None:
            from repro.core import schedule as cola_schedule
            print(cola_schedule.render_stream_footprint(
                args.cola_k, args.active, args.rounds, args.cola_d),
                flush=True)
        # compiled comm plans for arbitrary gossip topologies: color count,
        # the ppermute matchings, and per-link / per-device bytes per round
        # — the neighbor-only communication budget the topology-program
        # compiler (repro.topo) buys over the dense all-gather, rendered
        # for ANY registered graph, not just the circulant band. With
        # --cola-m the K-node graph is additionally quotiented onto M
        # devices (the block plan run_dist_cola executes on a mesh smaller
        # than the graph): block-level colors, per-link BLOCK bytes and the
        # intra- vs inter-block edge split.
        if args.topo != "none":
            from repro.core import schedule as cola_schedule
            from repro.core import topology as cola_topo
            from repro import topo as topo_programs
            wire = None if args.wire in (None, "fp32") else args.wire
            if args.cola_k > cola_schedule.DENSE_MAX_NODES:
                # a dense (K, K) adjacency/plan at this K would not fit —
                # the streamed cohort path above is the whole story
                print(f"[topology program] skipped: K={args.cola_k:,} > "
                      f"{cola_schedule.DENSE_MAX_NODES:,} "
                      "(dense adjacency/coloring does not materialize at "
                      "this population; sampled runs use the implicit "
                      "complete graph + streamed cohort schedule)",
                      flush=True)
                return
            for name in args.topo.split(","):
                graph = topo_programs.build(name.strip(), args.cola_k)
                plan = topo_programs.compile_plan(graph)
                beta = cola_topo.beta(cola_topo.metropolis_weights(graph))
                print(f"[topology program] {name.strip()} "
                      f"(graph={graph.name}, beta={beta:.4f})", flush=True)
                # the same budget repro.analysis verifies against the
                # compiled HLO — the render above is the plan's promise,
                # this line is the enforced contract (--wire swaps both to
                # the quantized payload + scale-sidecar accounting)
                print("  " + plan.contract(args.cola_d,
                                           wire=wire).describe(),
                      flush=True)
                print(plan.render(d=args.cola_d, wire=wire), flush=True)
                if args.cola_m and args.cola_m < args.cola_k:
                    bplan = topo_programs.compile_block_plan(graph,
                                                             args.cola_m)
                    print("  " + bplan.contract(args.cola_d,
                                                wire=wire).describe(),
                          flush=True)
                    print(bplan.render(d=args.cola_d, wire=wire),
                          flush=True)
        return

    os.makedirs(args.out, exist_ok=True)
    for a, s in pairs:
        tag = "multi" if args.multi_pod else "single"
        suffix = "" if opts.tag() == "baseline" else f"__{opts.tag()}"
        path = os.path.join(args.out, f"{a}__{s}__{tag}{suffix}.json")
        if os.path.exists(path) and not args.force:
            print(f"=== {a} x {s} [{tag}-pod] cached ===", flush=True)
            continue
        print(f"=== {a} x {s} [{tag}-pod] ===", flush=True)
        try:
            rep = lower_pair(a, s, multi_pod=args.multi_pod,
                             compile_=not args.no_compile, opts=opts)
        except Exception as e:  # record the failure, keep sweeping
            rep = {"arch": a, "shape": s, "mesh": tag, "status": "failed",
                   "error": f"{type(e).__name__}: {e}"[:500]}
        print(json.dumps(rep, indent=1), flush=True)
        with open(path, "w") as f:
            json.dump(rep, f, indent=1)


if __name__ == "__main__":
    main()
