"""Sharding rules: PartitionSpecs for every state buffer on the production mesh.

One rule set covers both workloads this repo runs:

* the **deep-net zoo** (dry-run / train / serve): FSDP+TP layout — every
  weight matrix puts its output (last) dimension on the ``model`` axis and
  its input dimension on the ``data`` axis, decode caches put batch on the
  data axes and head/feature dims on ``model``;
* the **CoLA state** (``repro.dist.runtime``): the node axis of every
  Algorithm-1 buffer (``x_parts`` (K, n_k), ``v_stack`` (K, d), schedules,
  metric rows) maps onto one mesh axis, so K nodes execute as K shards with
  no coordinator.

Every emitted spec is *divisibility-guarded*: an axis is assigned to a dim
only when the dim divides the mesh size for that axis (``sizes``), which is
what lets the dry-run's ``.lower()`` accept the in_shardings for all 10
architectures without per-arch special cases.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
from jax.sharding import AxisType, Mesh, PartitionSpec as P


@dataclasses.dataclass(frozen=True)
class MeshAxes:
    """Logical axis names of the production mesh.

    ``data`` carries batch + FSDP shards, ``model`` carries tensor-parallel
    shards, ``pod`` (multi-pod meshes only) carries the CoLA gossip node
    axis — one paper "node" per pod, neighbor exchange over ICI/DCN via
    ``lax.ppermute`` instead of a cross-pod all-reduce.
    """

    data: str = "data"
    model: str = "model"
    pod: str | None = None

    @property
    def batch_axes(self):
        """Axes the batch dimension shards over (pod-major when present)."""
        return (self.pod, self.data) if self.pod else self.data


def _size(sizes: dict, axis) -> int:
    if isinstance(axis, tuple):
        total = 1
        for a in axis:
            total *= sizes[a]
        return total
    return sizes[axis]


def _path_keys(path) -> list[str]:
    keys = []
    for e in path:
        if hasattr(e, "key"):
            keys.append(str(e.key))
        elif hasattr(e, "idx"):
            keys.append(str(e.idx))
    return keys


def _matrix_spec(shape, data_size: int, model_size: int, *, fsdp: bool,
                 expert_data_dim: int | None = None) -> P:
    """FSDP+TP spec for one weight leaf.

    ``model`` goes on the last divisible dim (output/column parallel,
    falling back to the second-to-last), ``data`` (FSDP) on the best
    remaining divisible dim scanning from the second-to-last backwards —
    leading stacked-layer axes participate only when they divide. With
    ``expert_data_dim`` the FSDP shards land on the experts axis instead
    (token-grouped MoE dispatch).
    """
    ndim = len(shape)
    entries: list = [None] * ndim
    if ndim < 2:
        return P()  # norms/biases: replicate
    model_dim = None
    for dim in (ndim - 1, ndim - 2):
        if shape[dim] % model_size == 0:
            model_dim = dim
            entries[dim] = "model"
            break
    if fsdp:
        if expert_data_dim is not None and expert_data_dim != model_dim \
                and shape[expert_data_dim] % data_size == 0:
            entries[expert_data_dim] = "data"
        else:
            for dim in range(ndim - 2, -1, -1):
                if dim != model_dim and shape[dim] % data_size == 0:
                    entries[dim] = "data"
                    break
            else:
                if model_dim != ndim - 1 and shape[-1] % data_size == 0:
                    entries[-1] = "data"
    return P(*entries)


def _rename(spec: P, axes: MeshAxes) -> P:
    table = {"data": axes.data, "model": axes.model, None: None}
    return P(*(table[a] for a in tuple(spec)))


def param_pspecs(params: Any, axes: MeshAxes, sizes: dict, *,
                 fsdp: bool = True, moe_output_fsdp: bool = False) -> Any:
    """PartitionSpec tree matching ``params`` (arrays or ShapeDtypeStructs).

    Args:
      axes: logical axis names (``MeshAxes``).
      sizes: mesh axis name -> size (``dict(zip(mesh.axis_names,
        mesh.devices.shape))``); used for divisibility guards.
      fsdp: shard the non-TP dim of every weight over ``axes.data``. Off for
        resident-weights serving (model-sharded only, no per-step gather).
      moe_output_fsdp: put the FSDP shards of expert tensors on the experts
        axis (expert-parallel grouping for token-grouped dispatch) instead
        of the feature dim.
    """
    data_size = sizes[axes.data]
    model_size = sizes[axes.model]

    def leaf_spec(path, leaf):
        keys = _path_keys(path)
        expert_dim = None
        if (moe_output_fsdp and len(keys) >= 2 and keys[-2] == "moe"
                and keys[-1] in ("w_gate", "w_up", "w_down")
                and len(leaf.shape) >= 3):
            expert_dim = len(leaf.shape) - 3  # (..., E, d_in, d_out)
        spec = _matrix_spec(leaf.shape, data_size, model_size, fsdp=fsdp,
                            expert_data_dim=expert_dim)
        return _rename(spec, axes)

    return jax.tree_util.tree_map_with_path(leaf_spec, params)


def cache_pspecs(cfg, cache: Any, global_batch: int, axes: MeshAxes,
                 sizes: dict) -> Any:
    """Decode-cache specs: batch on the data axes, trailing dim on ``model``.

    Cache leaves are stacked over scanned layer groups, so the layout is
    ``(L, B, ...)``: axis 0 replicates (scan carries it), axis 1 shards over
    ``axes.batch_axes`` when the batch divides (long-context B=1 decode
    replicates), and the last axis takes ``model`` when divisible (head_dim
    for KV caches, state/feature dims for SSM states). Only >=4-D leaves
    carry a feature axis — 3-D ones like the KV ``pos`` buffer end in the
    sequence axis, and TP-sharding positions would put a collective on
    every decode step's ring-buffer update.
    """
    batch_ax = axes.batch_axes
    b_size = _size(sizes, batch_ax)
    model_size = sizes[axes.model]

    def leaf_spec(leaf):
        shape = leaf.shape
        ndim = len(shape)
        if ndim < 2:
            return P()
        entries: list = [None] * ndim
        if ndim >= 2 and shape[1] == global_batch and global_batch % b_size == 0:
            entries[1] = batch_ax
        if ndim >= 4 and shape[-1] % model_size == 0:
            entries[-1] = axes.model
        return P(*entries)

    return jax.tree.map(leaf_spec, cache)


def batch_pspecs(cfg, shape, axes: MeshAxes) -> Any:
    """Input-batch specs: leading batch dim over ``axes.batch_axes``."""
    from repro.launch import specs as specs_lib

    sds = specs_lib.input_specs(cfg, shape)

    def leaf_spec(leaf):
        if len(leaf.shape) == 0:
            return P()
        return P(axes.batch_axes, *([None] * (len(leaf.shape) - 1)))

    return jax.tree.map(leaf_spec, sds)


# ---------------------------------------------------------------------------
# CoLA state (Algorithm 1) — the node axis onto a mesh axis
# ---------------------------------------------------------------------------

def auto_mesh(mesh: Mesh) -> Mesh:
    """``mesh`` with every axis Auto-typed: the same devices and names.

    ``jax.make_mesh`` types its axes Explicit, which puts each array's
    sharding into its type and refuses a ``vmap`` whose operands shard the
    mapped axis differently. The CoLA runtime places every node-axis buffer
    itself and leaves the global recompute outside ``shard_map``
    (recorders, telemetry) to GSPMD, so it runs on this view."""
    return Mesh(mesh.devices, mesh.axis_names,
                axis_types=(AxisType.Auto,) * len(mesh.axis_names))


def cola_state_pspecs(axis: str) -> Any:
    """Specs for ``ColaState``: both buffers (``x_parts`` (K, n_k) and
    ``v_stack`` (K, d)) put the node axis K on mesh axis ``axis``; a
    1-device axis degenerates to the single-host simulator layout."""
    return P(axis)


def cola_env_pspecs(axis: str) -> Any:
    """Specs for ``ColaEnv``: every per-node array (``a_parts`` (K, d, n_k),
    ``gp_parts``/``masks`` (K, n_k), ``gram_parts`` (K, n_k, n_k)) shards
    its leading node axis; nothing is replicated but the Problem constants
    baked into the compiled round program."""
    return P(axis)


def plan_payload_pspecs(axis: str) -> tuple:
    """Specs for the comm-plan payload (``repro.topo.PlanSchedule`` round
    slices): ``plan_diag`` (K,) shards its node axis, ``plan_coefs``
    (C, K) shards the node axis and replicates the color axis — so inside
    the shard_map round body each device reads exactly its own scalar
    coefficients (no W matrix, no gathers) and the ppermute perms are the
    only cross-device traffic of a plan-executed gossip step."""
    return (P(axis), P(None, axis))


def block_payload_pspec(axis: str) -> P:
    """Spec for the block-mode comm-plan payload
    (``repro.topo.BlockPlanSchedule`` round slices): the (K, K) round W
    shards its ROW axis over the node mesh axis, so each device reads its
    own (K/M, K) coefficient rows — the per-node weights it applies to the
    ppermute-assembled (K/M, ...) block payloads — and no device ever
    materializes another block's rows."""
    return P(axis)


def cola_counters_pspecs(axis: str) -> Any:
    """Specs for the telemetry ``obs.counters.Counters`` carry
    (``ColaState.counters`` when ``ColaConfig.telemetry=True``): the scalar
    accumulators (round/byte/permute/saturation/EF totals) replicate — they
    are the same number on every device by construction — and the per-sender
    ``gate`` (K,) rejection counter shards its node axis over ``axis`` like
    every other per-node row. Returned as a ``Counters`` of specs so
    ``jax.tree.map`` pairs leaves one-to-one with ``init_counters``."""
    from repro.obs.counters import Counters

    rep = P()
    return Counters(rounds=rep, wire_bytes=rep, permutes=rep,
                    sat_sum=rep, ef_sq=rep, gate=P(axis))


def cola_recorder_pspecs(axis: str, rec_state: Any) -> Any:
    """Specs for a recorder's per-run state (``Recorder.init_spec``): every
    array with a leading node dimension — the ``sigma_k`` spectral-norm
    cache (K,), the self-inclusive neighbor mask (K, K), the per-node
    problem blocks the certificate's condition (9) consumes — shards its
    node axis over ``axis``; scalars (thresholds, bounds) replicate. This is
    what keeps certificate record rounds gather-free: every operand of the
    shard_map record program is already node-sharded."""
    import numpy as np

    return jax.tree.map(
        lambda x: P(axis) if np.ndim(x) >= 1 else P(), rec_state)
