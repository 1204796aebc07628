"""Lower a ``CommPlan`` / ``BlockPlan`` to shard_map collectives.

These are the bodies ``repro.dist.runtime`` traces inside its shard_map
round/record programs when ``comm="plan"``. Two layouts:

* **one node per device** (``CommPlan``, K == mesh axis size): one
  ``lax.ppermute`` per node-level color, per-node coefficients fed from the
  ``PlanSchedule`` entries (sharded over the node axis, so each device sees
  its own scalars);
* **node blocks** (``BlockPlan``, K/M contiguous nodes per device, M < K):
  one ``lax.ppermute`` of the whole (K/M, d) block payload per BLOCK-level
  color, assembled into a zero-filled (K, d) neighborhood buffer and
  contracted against this device's (K/M, K) W-row slice in one dot
  (``block_mix_step``). Intra-block edges ride the dot as local terms —
  zero communication.

Every exchange (the permutes, and the assembly of a block's neighborhood
buffer) runs under the device scope ``cola.exchange``
(``jax.named_scope``), nested in the round's ``cola.mix`` or the
recorder's ``cola.record``.

Nothing here gathers a (K, ...) stack collectively — the whole point of
the compiler is that the lowered HLO contains collective-permutes of block-
sized payloads only, which the dist tests assert via ``launch.hlo_analysis``.

Semantics contracts (pinned by the property/parity tests):

* ``plan_mix_step(v_k, ...) == dense_mix(w, v_stack)[k]`` up to float
  summation order (self term first, then colors in order, matching
  ``plan.plan_mix_dense``);
* ``block_mix_step(v_block, ...) == dense_mix(w, v_stack)[block]``
  BITWISE — the buffer dot runs the same length-K contraction as the
  simulator's (K, K) @ (K, d) matmul, with exact zeros where no exchange
  happened (and where W is zero anyway). This is what makes
  ``run_dist_cola(comm="plan")`` on 1/2/4 devices bit-identical to
  ``run_cola``.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core import mixing, quant
from repro.core.precision import MATMUL
from repro.topo.plan import BlockPlan, CommPlan


def plan_mix_step(v_local, axis_name: str, plan: CommPlan, diag, coefs):
    """One compiled gossip step for THIS device's node state.

    Args:
      v_local: this node's state, any shape (the node index is the position
        along ``axis_name``; one node per device).
      diag: scalar W_kk for this node (the node-sharded ``plan_diag`` slice).
      coefs: (C,) per-color coefficients W[k, partner_c(k)] for this node
        (the node-sharded ``plan_coefs`` slice; 0 where unmatched or where
        churn reweighting dropped the edge this round).
    """
    out = diag * v_local
    for c, perm in enumerate(plan.perms):
        # a matching's swap involution: unmatched devices receive zeros,
        # and their coefficient is 0 by construction — no conditional needed
        with jax.named_scope("cola.exchange"):
            recv = lax.ppermute(v_local, axis_name, list(perm))
        out = out + coefs[c] * recv
    return out


def plan_mix_steps(v_local, axis_name: str, plan: CommPlan, diag, coefs,
                   steps: int):
    """B consecutive gossip steps (App. E.2): the sequential form W^B v.

    The dense path folds W first (cheap in K); on the wire the fold does
    not exist — each step exchanges neighbor-only traffic, so B steps cost
    B * num_colors ppermutes, exactly the paper's B-step communication
    model. ``steps`` is a static Python int (unrolled at trace time).
    """
    out = v_local
    for _ in range(steps):
        out = plan_mix_step(out, axis_name, plan, diag, coefs)
    return out


def plan_mix_steps_wire(v_send, v_self, axis_name: str, plan: CommPlan,
                        diag, coefs, steps: int):
    """``plan_mix_steps`` where the FIRST step's payload may be a wire lie
    (``repro.attack``): the node ppermutes ``v_send`` but its own W_kk term
    uses its honest ``v_self`` (pass None for the honest fast path). Later
    steps re-mix received values, which are honest."""
    if v_self is None or steps <= 0:
        return plan_mix_steps(v_send, axis_name, plan, diag, coefs, steps)
    first = plan_mix_step(v_send, axis_name, plan, diag, coefs)
    first = first + diag * (v_self - v_send)
    return plan_mix_steps(first, axis_name, plan, diag, coefs, steps - 1)


def block_gather_neighbors(x_block, axis_name: str, plan: BlockPlan):
    """Assemble the (K, width) node stack this device can SEE: its own
    (K/M, ...) block plus one ppermuted block per block-level color, written
    at the partner block's node rows; blocks of never-exchanged devices stay
    zero. One ppermute per color — the only collectives of the block path
    (no all-gather anywhere), shared by the mixing step and the
    certificate's Eq.-10 neighborhood exchange.
    """
    ln = plan.local_nodes
    flat = x_block.reshape(ln, -1)
    i = lax.axis_index(axis_name)
    partners = jnp.asarray(plan.block.partner_arrays())     # (C, M) static
    with jax.named_scope("cola.exchange"):
        buf = jnp.zeros((plan.num_nodes, flat.shape[1]), flat.dtype)
        buf = lax.dynamic_update_slice_in_dim(buf, flat, i * ln, 0)
        for c, perm in enumerate(plan.block.perms):
            recv = lax.ppermute(flat, axis_name, list(perm))
            src = partners[c, i]
            # unmatched devices receive ppermute zero-fill and src == i:
            # write the own block back instead of clobbering it with zeros
            buf = lax.dynamic_update_slice_in_dim(
                buf, jnp.where(src != i, recv, flat), src * ln, 0)
    return buf


def block_mix_step(v_block, axis_name: str, plan: BlockPlan, w_rows):
    """One gossip step for THIS device's (K/M, ...) node block.

    Args:
      v_block: the device's node block, leading dim K/M.
      w_rows: (K/M, K) — this device's rows of the round's W (the
        node-sharded ``plan_w`` slice from ``BlockPlanSchedule``). Entries
        addressing nodes outside the assembled neighborhood are zero by the
        coverage contract, so the dot equals the dense (K, K) mix bitwise.
    """
    flat = v_block.reshape(v_block.shape[0], -1)
    buf = block_gather_neighbors(flat, axis_name, plan)
    out = jnp.matmul(w_rows.astype(flat.dtype), buf, precision=MATMUL)
    return out.reshape(v_block.shape)


def block_mix_steps(v_block, axis_name: str, plan: BlockPlan, w_rows,
                    steps: int):
    """B consecutive block-mode gossip steps (App. E.2), sequential on the
    wire like ``plan_mix_steps``: B * num_colors block ppermutes."""
    out = v_block
    for _ in range(steps):
        out = block_mix_step(out, axis_name, plan, w_rows)
    return out


def block_mix_steps_wire(v_send, v_self, axis_name: str, plan: BlockPlan,
                         w_rows, steps: int):
    """``block_mix_steps`` where the FIRST step's payload may be a wire lie
    (``repro.attack``): each node of the block sends ``v_send`` but its own
    W_kk term uses its honest ``v_self`` (pass None for the honest fast
    path). Later steps re-mix received values, which are honest."""
    if v_self is None or steps <= 0:
        return block_mix_steps(v_send, axis_name, plan, w_rows, steps)
    ln = plan.local_nodes
    first = block_mix_step(v_send, axis_name, plan, w_rows)
    row_ids = lax.axis_index(axis_name) * ln + jnp.arange(ln)
    diag = jnp.take_along_axis(w_rows, row_ids[:, None], axis=1)  # (ln, 1)
    delta = (v_self - v_send).reshape(ln, -1)
    first = first + (diag.astype(delta.dtype) * delta).reshape(v_send.shape)
    return block_mix_steps(first, axis_name, plan, w_rows, steps - 1)


# ---------------------------------------------------------------------------
# quantized wire: ppermute int8/fp8 payloads + fp32 scale sidecars
# ---------------------------------------------------------------------------

def ppermute_wire(q, axis_name: str, perm):
    """``lax.ppermute`` of a quantized payload as RAW BYTES.

    Some backends legalize float8 collectives by upcasting the operand to
    f16 — which would silently double the wire bytes the comm contracts
    cap. Bitcasting the payload to uint8 for the permute (and back after)
    keeps every quantized payload 1 byte/elem on every backend; the bit
    pattern — and hence the dequantized value — is untouched.
    """
    if q.dtype.itemsize == 1 and jnp.issubdtype(q.dtype, jnp.floating):
        raw = lax.ppermute(lax.bitcast_convert_type(q, jnp.uint8),
                           axis_name, perm)
        return lax.bitcast_convert_type(raw, q.dtype)
    return lax.ppermute(q, axis_name, perm)


def plan_qmix_steps(v_local, ef_local, axis_name: str, plan: CommPlan,
                    diag, coefs, steps: int, wire: str, round_key,
                    payload=None):
    """B quantized gossip steps for THIS device's node (one node/device).

    Each step the node encodes its value once (EF-compensated when
    ``ef_local`` is not None, stochastic rounding keyed per
    (round, step, node)), ppermutes the narrow payload PLUS its fp32
    absmax scale sidecar on every color, and dequantizes what arrives
    before the coefficient contraction.  The self term uses the node's own
    dequantized payload — the device-count-invariant wire view
    ``quant.wire_view`` defines, so this equals the simulator's
    ``dense_mix(w, deq)`` rows to float summation order (the same
    tolerance contract as the fp32 plan path).

    ``payload``: optional pre-encoded ``(q, scale)`` for the FIRST step —
    the pipelined executor's double buffer, encoded at the end of the
    previous round with this round's key (EF already folded then).
    Returns ``(mixed, ef_new)``.
    """
    i = lax.axis_index(axis_name)
    out, ef = v_local, ef_local
    for s in range(steps):
        flat = out.reshape(-1)
        if s == 0 and payload is not None:
            q, sc = payload
            deq = quant.dequantize(q, sc)
        else:
            k = None if round_key is None else \
                jax.random.fold_in(quant.step_key(round_key, s), i)
            p = flat if ef is None else flat + ef.reshape(-1)
            q, sc = quant.quantize(p, wire, k)
            deq = quant.dequantize(q, sc)
            if ef is not None:
                ef = (p - deq).reshape(ef.shape)
        acc = diag * deq
        for c, perm in enumerate(plan.perms):
            with jax.named_scope("cola.exchange"):
                rq = ppermute_wire(q, axis_name, list(perm))
                rs = lax.ppermute(sc, axis_name, list(perm))
            acc = acc + coefs[c] * quant.dequantize(rq, rs)
        out = acc.reshape(out.shape)
    return out, ef


def block_gather_neighbors_q(q, scale, deq, axis_name: str, plan: BlockPlan):
    """Quantized-wire ``block_gather_neighbors``: ppermute the (K/M, d)
    narrow payload + (K/M, 1) scale sidecar per block color and dequantize
    into the zero-filled (K, d) neighborhood buffer.  The device's own
    rows hold its own DEQUANTIZED payload (``deq``) — every contribution,
    local or remote, goes through the same codec, which is what keeps the
    buffer dot bitwise-equal to ``dense_mix`` on the dequantized stack for
    any mesh size."""
    ln = plan.local_nodes
    i = lax.axis_index(axis_name)
    partners = jnp.asarray(plan.block.partner_arrays())     # (C, M) static
    with jax.named_scope("cola.exchange"):
        buf = jnp.zeros((plan.num_nodes, deq.shape[1]), deq.dtype)
        buf = lax.dynamic_update_slice_in_dim(buf, deq, i * ln, 0)
        for c, perm in enumerate(plan.block.perms):
            rq = ppermute_wire(q, axis_name, list(perm))
            rs = lax.ppermute(scale, axis_name, list(perm))
            recv = quant.dequantize(rq, rs)
            src = partners[c, i]
            buf = lax.dynamic_update_slice_in_dim(
                buf, jnp.where(src != i, recv, deq), src * ln, 0)
    return buf


def block_qmix_steps(v_block, ef_block, axis_name: str, plan: BlockPlan,
                     w_rows, steps: int, wire: str, round_key,
                     payload=None):
    """B quantized block-mode gossip steps (see ``plan_qmix_steps``).

    Per step: encode this device's (K/M, d) block once (per-node-row
    absmax scales, per-node SR keys from the GLOBAL node ids, EF folded
    when ``ef_block`` is not None), ppermute payload + sidecar per block
    color, dequantize into the neighborhood buffer, contract against the
    W rows in one dot — bitwise the simulator's
    ``dense_mix(w, quant.wire_view(v))`` rows.  Returns
    ``(mixed, ef_new)``.
    """
    ln = plan.local_nodes
    row_ids = lax.axis_index(axis_name) * ln + jnp.arange(ln)
    out, ef = v_block, ef_block
    for s in range(steps):
        flat = out.reshape(ln, -1)
        if s == 0 and payload is not None:
            q, sc = payload
        else:
            k = None if round_key is None else quant.step_key(round_key, s)
            p = flat if ef is None else flat + ef.reshape(ln, -1)
            q, sc = quant.quantize_rows(p, wire, k, node_ids=row_ids)
            if ef is not None:
                ef = (p - quant.dequantize(q, sc)).reshape(ef.shape)
        deq = quant.dequantize(q, sc)
        buf = block_gather_neighbors_q(q, sc, deq, axis_name, plan)
        out = jnp.matmul(w_rows.astype(deq.dtype), buf,
                         precision=MATMUL).reshape(out.shape)
    return out, ef


def block_robust_qmix_step(v_block, ef_block, axis_name: str,
                           plan: BlockPlan, w_rows, wire: str, round_key,
                           mode: str, *, trim: int = 1,
                           clip: float | None = None):
    """ONE robust gossip step on a QUANTIZED wire — the composed
    ``cfg.robust`` x ``cfg.wire`` lowering for the block plan path.

    Encodes this device's block exactly like ``block_qmix_steps`` (per-node
    absmax rows, SR keys from GLOBAL node ids, EF folded), ppermutes the
    narrow payload + sidecar per block color into the DEQUANTIZED
    neighborhood buffer, then aggregates each node row with
    ``mixing.robust_neighborhood_mix`` instead of the linear dot — so the
    outlier gate judges the same dequantized values the receivers would
    consume, bitwise the simulator's composed branch in
    ``cola._round_body`` (trim/median; clip is allclose, see
    ``block_robust_mix_step``). Single step by construction: the composed
    wire is scoped to ``gossip_steps == 1`` (re-encoding mixed values is
    unmodeled), which ``cola._check_wire_config`` enforces up front.
    Returns ``(mixed, ef_new)``.
    """
    ln = plan.local_nodes
    row_ids = lax.axis_index(axis_name) * ln + jnp.arange(ln)
    flat = v_block.reshape(ln, -1)
    key = None if round_key is None else quant.step_key(round_key, 0)
    p = flat if ef_block is None else flat + ef_block.reshape(ln, -1)
    q, sc = quant.quantize_rows(p, wire, key, node_ids=row_ids)
    deq = quant.dequantize(q, sc)
    ef_new = (None if ef_block is None
              else (p - deq).reshape(ef_block.shape))
    buf = block_gather_neighbors_q(q, sc, deq, axis_name, plan)   # (K, d)
    out = mixing.robust_neighborhood_mix(w_rows, buf, row_ids, mode,
                                         trim=trim, clip=clip,
                                         self_override=None)
    return out.reshape(v_block.shape).astype(v_block.dtype), ef_new


def block_robust_mix_step(v_block, axis_name: str, plan: BlockPlan, w_rows,
                          mode: str, *, trim: int = 1,
                          clip: float | None = None, v_self=None):
    """One ROBUST gossip step for THIS device's (K/M, ...) node block: the
    Byzantine-resilient replacement for ``block_mix_step``'s dot.

    Assembles the same ppermute neighborhood buffer, then aggregates each of
    this device's node rows with ``mixing.robust_neighborhood_mix`` (trimmed
    mean / median / norm clipping) instead of the linear W contraction. The
    robust rule depends only on buffer slots inside each node's W-row
    support — which the coverage contract guarantees were exchanged — so the
    result is BITWISE the simulator's ``mixing.robust_mix_dense`` on every
    mesh size, exactly like the linear block path.

    Bitwise caveat: the guarantee holds for ``mode="trim"`` / ``"median"``
    (selection + the shared weighted einsum). ``mode="clip"`` adds a
    sqrt/divide chain (deviation norms -> tau / norm scale) that XLA fuses
    differently inside the full scanned round program depending on the
    shard shape — a standalone call is bitwise on every mesh, but whole
    attacked runs drift by ~1 ulp (observed 6e-8) on multi-device meshes.
    End-to-end parity for clip is therefore allclose, not bitwise.

    ``v_self`` (same shape as ``v_block``) supplies each node's honest state
    when ``v_block`` is an attacked wire payload: the node's own buffer slot
    is overridden so a liar's lie travels to neighbors but never enters its
    own aggregate (wire-only attack semantics).
    """
    ln = plan.local_nodes
    flat = v_block.reshape(ln, -1)
    buf = block_gather_neighbors(flat, axis_name, plan)          # (K, d)
    row_ids = lax.axis_index(axis_name) * ln + jnp.arange(ln)
    ov = None if v_self is None else v_self.reshape(ln, -1)
    out = mixing.robust_neighborhood_mix(w_rows, buf, row_ids, mode,
                                         trim=trim, clip=clip,
                                         self_override=ov)
    return out.reshape(v_block.shape).astype(v_block.dtype)


def block_robust_mix_steps(v_block, axis_name: str, plan: BlockPlan, w_rows,
                           mode: str, *, trim: int = 1,
                           clip: float | None = None, steps: int = 1,
                           v_self=None):
    """B consecutive robust block-mode gossip steps — sequential on the wire
    (robust aggregation has no W^B fold), matching
    ``mixing.robust_mix_steps`` bitwise. ``v_self`` applies to the first
    step only: after one exchange the circulating values are honest."""
    out = v_block
    for i in range(steps):
        out = block_robust_mix_step(out, axis_name, plan, w_rows, mode,
                                    trim=trim, clip=clip,
                                    v_self=v_self if i == 0 else None)
    return out


def block_neighborhood_stats(g_block, axis_name: str, plan: BlockPlan,
                             mask_rows):
    """(masked neighbor sums, neighborhood sizes) for the Prop.-1
    certificate in block mode: exchange this device's (K/M, d) local
    gradients over the block-level colors and mask-select per node.

    ``mask_rows`` is the device's (K/M, K) slice of the self-inclusive 0/1
    neighborhood mask (static graph, or the churn round's reweighted-support
    rows from the certificate schedule). Masked-out buffer rows are exact
    zeros, so the result equals the stacked ``duality.neighborhood_mean``
    numerator/denominator bitwise. O(num_colors * (K/M) * d) bytes per
    device; no stack gathers.
    """
    mask_rows = jnp.asarray(mask_rows)
    buf = block_gather_neighbors(g_block, axis_name, plan)   # (K, d)
    sel = jnp.where(mask_rows[:, :, None] > 0, buf[None, :, :], 0.0)
    return jnp.sum(sel, axis=1), jnp.sum(mask_rows, axis=1)  # (ln, d), (ln,)


def plan_neighborhood_stats(g_local, axis_name: str, plan: CommPlan,
                            mask_row):
    """(masked neighbor sum, neighborhood size) for the Prop.-1 certificate.

    Exchanges THIS device's (d,)-vector ``g_local`` (the local gradient)
    over the plan's permutations and mask-selects what arrives:
    ``mask_row`` is this node's row of the self-inclusive 0/1 neighborhood
    mask — the static graph's row, or the churn round's reweighted-support
    row from the certificate schedule, in which case dropped neighbors
    contribute 0 exactly as the stacked ``duality.neighborhood_mean``
    oracle excludes them. O(num_colors * d) bytes per device; no stack
    gathers.
    """
    mask_row = jnp.asarray(mask_row)
    i = lax.axis_index(axis_name)
    partners = jnp.asarray(plan.partner_arrays())          # (C, K) static
    nsum = mask_row[i] * g_local                            # self (mask=1)
    for c, perm in enumerate(plan.perms):
        with jax.named_scope("cola.exchange"):
            recv = lax.ppermute(g_local, axis_name, list(perm))
        nsum = nsum + mask_row[partners[c, i]] * recv
    return nsum, jnp.sum(mask_row)


def comm_budget(plan, d: int, itemsize: int = 4, *,
                gossip_steps: int = 1, wire: str | None = None) -> dict:
    """The collective budget this module's lowerings emit for ``plan``.

    ``plan_mix_steps`` / ``block_mix_steps`` (and their wire/robust
    variants) issue exactly ``num_colors`` ``lax.ppermute`` ops per gossip
    step — one per color class — each carrying a (d,) vector (per-node
    plan) or a (K/M, d) block payload. On a quantized wire
    (``plan_qmix_steps`` / ``block_qmix_steps``) each color ppermutes TWO
    tensors — the narrow payload and its fp32 scale sidecar — so the count
    doubles while the bytes drop ~4x. This is the single source of truth
    behind ``CommPlan.contract`` / ``BlockPlan.contract``: the budget is a
    property of HOW the plan lowers, so it lives next to the lowerings.
    """
    from repro.topo.plan import _permutes_per_step
    return {
        "collective_permutes":
            gossip_steps * _permutes_per_step(plan.num_colors, wire),
        "bytes_per_device":
            gossip_steps * plan.bytes_per_device_per_step(d, itemsize,
                                                          wire=wire),
    }
