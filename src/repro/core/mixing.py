"""Gossip mixing operators: v_k <- sum_l W_kl v_l  (Algorithm 1, step 4).

Three executable paths with identical semantics (validated against each
other in tests):

* ``dense_mix`` — a (K, K) x (K, d) matmul on stacked node state. Used by the
  single-host simulator (vmapped over nodes) and as the oracle for arbitrary
  graphs.
* ``ring_mix_ppermute`` — a shard_map body using ``lax.ppermute`` neighbor
  exchanges for banded (c-connected-cycle / ring) mixing matrices. This is the
  TPU-native adaptation: each gossip round costs only deg(k) * |v| bytes per
  ICI link instead of a full all-reduce, which is exactly the paper's
  communication-efficiency argument transcribed to pod hardware. Retained as
  the circulant special case (and for bitwise compatibility of historical
  ring runs).
* the **topology-program path** (``repro.topo``) — the general form:
  ``compile_plan`` edge-colors ANY sparse W's support into matchings, each
  lowered to one ``ppermute`` (``repro.topo.lowering.plan_mix_step``), with
  per-round weight coefficients riding the executor schedule. This is what
  ``repro.dist.runtime`` executes for non-circulant and churn-reweighted
  (time-varying) graphs; ``check_circulant_band`` below is the ring path's
  validity gate, ``repro.topo.check_plan_covers`` its generalization.

``mix_power`` applies B gossip steps (time-varying-graph extension, App. E.2).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import lax

from repro.core.precision import MATMUL


def dense_mix(w: jax.Array, v_stack: jax.Array) -> jax.Array:
    """v'_k = sum_l W_kl v_l for stacked node state.

    Args:
      w: (K, K) mixing matrix.
      v_stack: (K, ...) per-node state stacked on axis 0.
    """
    flat = v_stack.reshape(v_stack.shape[0], -1)
    out = jnp.matmul(w.astype(flat.dtype), flat, precision=MATMUL)
    return out.reshape(v_stack.shape)


def mix_power(w: jax.Array, v_stack: jax.Array, steps: int) -> jax.Array:
    """Apply B consecutive gossip steps (App. E.2 time-varying extension).

    For B >= 2 the B-step mix (W^B) v is computed by folding W first:
    B-1 (K, K) matmuls + one (K, d) mix — O(B K^3 + K^2 d) instead of the
    sequential O(B K^2 d), a win whenever the node state is larger than the
    node count (d > K, the only regime the paper cares about). B is a static
    Python int, so the fold unrolls at trace time.
    """
    if steps <= 0:
        return v_stack
    if steps == 1:
        return dense_mix(w, v_stack)
    w_pow = w
    for _ in range(steps - 1):
        w_pow = jnp.matmul(w, w_pow, precision=MATMUL)
    return dense_mix(w_pow, v_stack)


def mix_power_wire(w: jax.Array, v_send: jax.Array,
                   v_self: jax.Array | None, steps: int) -> jax.Array:
    """B gossip steps where the FIRST step mixes on-the-wire payloads.

    ``v_send`` is what each node emitted (possibly a Byzantine lie — see
    ``repro.attack``); ``v_self`` is the stacked honest state, or None when
    nothing was corrupted (the fast path is then exactly ``mix_power``).
    A lie only exists on the wire: each receiving node's OWN contribution
    W_kk v_k uses its honest state, so the first step is
    ``W v_send + diag(W) (v_self - v_send)``; the remaining B-1 steps mix
    the already-received values honestly."""
    if v_self is None or steps <= 0:
        return mix_power(w, v_send, steps)
    first = dense_mix(w, v_send)
    diag = jnp.diagonal(w).astype(first.dtype)
    first = first + diag[:, None] * (v_self - v_send)
    return mix_power(w, first, steps - 1)


def qmix_steps(w: jax.Array, v_stack: jax.Array, ef, steps: int, wire: str,
               round_key, node_ids=None, payload=None):
    """B gossip steps over a QUANTIZED wire (simulator / dense oracle).

    Every step, each node encodes its current value once (EF-compensated
    when ``ef`` is not None, stochastic rounding keyed per
    (round, step, node) — ``quant.wire_view``) and the whole mix runs on
    the dequantized stack: ``W @ deq``.  All contributions — including the
    node's own diagonal term — go through the codec, so the function is
    independent of how rows are later sharded; the plan and block
    lowerings (``repro.topo.lowering.plan_qmix_steps`` /
    ``block_qmix_steps``) reproduce it to the same tolerance contracts as
    their fp32 counterparts (allclose / bitwise).

    ``payload``: optional pre-encoded ``(q, scale)`` for the first step
    (the pipelined executor's double buffer).  Returns ``(mixed, ef_new)``.
    """
    from repro.core import quant

    out = v_stack
    for s in range(steps):
        if s == 0 and payload is not None:
            deq = quant.dequantize(*payload)
        else:
            k = None if round_key is None else quant.step_key(round_key, s)
            p = out if ef is None else out + ef
            q, sc = quant.quantize_rows(p, wire, k, node_ids=node_ids)
            deq = quant.dequantize(q, sc)
            if ef is not None:
                ef = p - deq
        out = dense_mix(w, deq)
    return out, ef


def banded_weights(w: jax.Array, conn: int) -> jax.Array:
    """Extract (2*conn+1,) banded weights [w_-c..w_0..w_+c] from a circulant W.

    ASSUMES W is circulant-banded (ring or c-connected cycle with uniform
    Metropolis weights); ``w`` is usually traced here, so no mass check is
    possible — callers with a concrete W validate via
    ``check_circulant_band`` before entering jit.
    """
    k = w.shape[0]
    offs = jnp.arange(-conn, conn + 1)
    rows = jnp.arange(k)
    cols = (rows[None, :] + offs[:, None]) % k
    band = w[rows[None, :], cols]  # (2c+1, K)
    return band[:, 0]


def check_circulant_band(w, conn: int, atol: float = 1e-6) -> None:
    """Raise ValueError unless the CONCRETE matrix ``w`` is circulant with
    bandwidth <= ``conn`` — i.e. the banded ppermute mixing reproduces the
    full W matmul exactly (no weight mass outside the band, no row
    variation the band extraction would silently drop)."""
    import numpy as np

    w = np.asarray(w)
    k = w.shape[0]
    band = np.asarray(banded_weights(jnp.asarray(w), conn))
    rows, offs = np.arange(k), np.arange(-conn, conn + 1)
    rebuilt = np.zeros_like(w)
    rebuilt[rows[None, :], (rows[None, :] + offs[:, None]) % k] = \
        band[:, None]
    if not np.allclose(w, rebuilt, atol=atol):
        raise ValueError(
            f"W is not circulant-banded with connectivity {conn}: banded "
            f"ppermute mixing would drop {np.abs(w - rebuilt).max():.3g} of "
            "weight mass — use the dense mixing path for this graph")


def ring_mix_ppermute(v_local: jax.Array, axis_name: str, weights: jax.Array,
                      conn: int = 1) -> jax.Array:
    """Gossip step inside shard_map: banded circulant mixing via ppermute.

    Args:
      v_local: this node's state (any shape); the node index is the position
        along ``axis_name``.
      axis_name: mesh axis carrying the K nodes.
      weights: (2*conn+1,) band [w_{-conn}, ..., w_0, ..., w_{+conn}].
      conn: connectivity (1 = ring, 2 = 2-connected cycle, ...).
    """
    k = lax.axis_size(axis_name)
    out = weights[conn] * v_local
    for off in range(1, conn + 1):
        # receive from left neighbor at distance `off`
        perm_l = [((i + off) % k, i) for i in range(k)]
        from_right = lax.ppermute(v_local, axis_name, [(i, (i + off) % k) for i in range(k)])
        from_left = lax.ppermute(v_local, axis_name, perm_l)
        out = out + weights[conn + off] * from_left + weights[conn - off] * from_right
    return out


def dense_mix_shardmap(v_local: jax.Array, axis_name: str, w: jax.Array) -> jax.Array:
    """Gossip step inside shard_map for arbitrary W: all-gather + weighted sum.

    Fallback for non-circulant graphs; costs an all-gather of v (K*|v| bytes).
    """
    idx = lax.axis_index(axis_name)
    v_all = lax.all_gather(v_local, axis_name)  # (K, ...)
    return dense_mix(w, v_all)[idx]


# ---------------------------------------------------------------------------
# robust (Byzantine-resilient) aggregation
# ---------------------------------------------------------------------------

ROBUST_MODES = ("trim", "median", "clip")

# adaptive clip radius = factor x median neighbor deviation norm: > 1 so the
# honest spread passes unclipped (see robust_neighborhood_mix docstring)
_CLIP_TAU_FACTOR = 3.0

# outlier gates for trim/median: a neighbor is distrusted when its payload
# is anti-correlated with the neighborhood's coordinate-median center
# (cosine below _TRIM_COS_GATE — honest estimates of the same dual point
# stay positively correlated once mixing starts, dipping just below 0 only
# on the heterogeneous first rounds, while a sign-flipped payload reads
# ~-0.7 against a healthy center) or when its norm exceeds
# _TRIM_NORM_GATE x the LARGEST other neighbor norm (inflation attacks;
# the leave-one-out max — unlike a median — survives the near-zero payload
# norms lasso-type problems emit while most blocks are still inactive).
# Honest neighbors trip neither, so a clean defended run is the linear mix
# bit-for-bit and the Lemma-1 invariant the certificate audits holds to
# float precision.
_TRIM_COS_GATE = -0.2
_TRIM_NORM_GATE = 3.0
# the norm gate only ARMS when the center is informative (nonzero) and the
# payload is not positively aligned with it: early-round honest spikes are
# 3-11x their neighbors in norm (heterogeneous data blocks activate at
# different times) but always correlate positively with a nonzero center,
# while an inflation lie big enough to matter cannot afford to point along
# the consensus estimate (aligned inflation is bounded-influence: it only
# accelerates the direction the cohort already agreed on)
_TRIM_NORM_ARM_COS = 0.2


def _masked_trimmed_mean(vals, mask, b_counts, counts):
    """Coordinate-wise trimmed mean over the masked slots of ``vals``.

    vals (R, K, d): candidate values; slots with mask == False are ignored.
    b_counts (R,): how many extremes to drop from EACH side per row.
    counts (R,): masked slot count per row. Masked-out slots are replaced by
    the dtype's max sentinel so every row's sort places them past the kept
    window — the result depends only on masked values, which is what makes
    the simulator (true values everywhere) and the block lowering (zeros at
    never-exchanged slots) produce bitwise-identical rows.
    """
    big = jnp.asarray(jnp.finfo(vals.dtype).max, vals.dtype)
    guarded = jnp.where(mask[:, :, None], vals, big)
    srt = jnp.sort(guarded, axis=1)
    idx = jnp.arange(vals.shape[1])[None, :, None]
    lo = b_counts[:, None, None]
    hi = (counts - b_counts)[:, None, None]
    keep = (idx >= lo) & (idx < hi)
    kept = jnp.sum(jnp.where(keep, srt, 0.0), axis=1)
    denom = jnp.maximum(counts - 2 * b_counts, 1).astype(vals.dtype)
    return kept / denom[:, None]


def _neighborhood_setup(w_rows, buf, row_ids, self_override):
    """Shared masking/value setup of the robust aggregation and its gate.

    Returns ``(flat, w_rows, self_hot, mask, counts, self_vals, vals)`` —
    exactly the quantities ``robust_neighborhood_mix`` computes before
    branching on the mode, factored out so :func:`gate_flags` sees the SAME
    operations (XLA CSEs the two when both are traced into one program,
    which is what makes the telemetry gate counter free on defended runs).
    """
    k = buf.shape[0]
    flat = buf.reshape(k, -1)
    w_rows = jnp.asarray(w_rows, dtype=flat.dtype)
    row_ids = jnp.asarray(row_ids)
    r = row_ids.shape[0]
    self_hot = jnp.arange(k)[None, :] == row_ids[:, None]        # (R, K)
    mask = (w_rows != 0) | self_hot
    counts = jnp.sum(mask.astype(jnp.int32), axis=1)             # (R,)

    self_vals = (flat[row_ids] if self_override is None
                 else self_override.reshape(r, -1).astype(flat.dtype))
    vals = jnp.broadcast_to(flat[None, :, :], (r, k, flat.shape[1]))
    if self_override is not None:
        # wire-only attacks: the receiver's own slot carries its honest
        # state, not the payload it emitted to everyone else
        vals = jnp.where(self_hot[:, :, None], self_vals[:, None, :], vals)
    return flat, w_rows, self_hot, mask, counts, self_vals, vals


def _gate_center_flags(vals, mask, self_hot, counts, trim):
    """Robust center + per-neighbor outlier gate for trim/median modes.

    Returns ``(center, flagged)``: the coordinate-median neighborhood
    center (R, d) and the (R, K) flag mask (True = this receiver rejects
    that sender's edge this step; self slots never flag).
    """
    r, k, dflat = vals.shape
    # coordinate-wise neighborhood order statistics: masked-out slots
    # sort past every real value (sentinel), so positions 0..counts-1
    # are exactly the neighborhood — identical in sim (true values at
    # never-exchanged slots) and block (zeros there) buffers, which is
    # what keeps the two paths bitwise equal
    big = jnp.asarray(jnp.finfo(vals.dtype).max, vals.dtype)
    guarded = jnp.where(mask[:, :, None], vals, big)
    target = (counts - 1) // 2
    if k <= 32:
        # rank selection: the (counts-1)//2-th order statistic via an
        # O(K^2) comparison count instead of a sort — XLA's CPU sort
        # custom-call costs ~4x more than these fused elementwise
        # reductions at gossip-neighborhood sizes, and the robust mix
        # runs every round of every defended run. Index tie-breaking
        # gives each slot a unique rank, and tied slots carry equal
        # values, so the selected VALUE is bitwise the sorted one's.
        lt = guarded[:, :, None, :] < guarded[:, None, :, :]
        eq = guarded[:, :, None, :] == guarded[:, None, :, :]
        ilt = (jnp.arange(k)[:, None]
               < jnp.arange(k)[None, :])[None, :, :, None]
        rank = jnp.sum(lt | (eq & ilt), axis=1)              # (R, K, d)
        sel = rank == target[:, None, None]
        center = jnp.sum(jnp.where(sel, guarded, 0.0), axis=1)
    else:
        # large neighborhoods: the (R, K^2, d) comparison tensor stops
        # paying for itself — fall back to the sort
        srt = jnp.sort(guarded, axis=1)
        center = jnp.take_along_axis(
            srt, jnp.broadcast_to(target[:, None, None],
                                  (r, 1, dflat)), axis=1)[:, 0]
    # per-NEIGHBOR outlier gate on whole-vector geometry (see the
    # robust_neighborhood_mix docstring): anti-correlation with the robust
    # center, or norm inflation vs the (trim+1)-th largest neighbor norm —
    # a reference that `trim` colluding inflated payloads cannot raise.
    # Neither statistic fires on honest payloads, so the unflagged path is
    # the linear mix bit-for-bit.
    norms = jnp.sqrt(jnp.sum(vals * vals, axis=-1))          # (R, K)
    cnorm = jnp.sqrt(jnp.sum(center * center, axis=-1))      # (R,)
    dots = jnp.einsum("rkd,rd->rk", vals, center, precision=MATMUL)
    cos = dots / (norms * cnorm[:, None] + 1e-30)
    nb_mask = mask & ~self_hot
    m_nb = jnp.sum(nb_mask.astype(jnp.int32), axis=1)
    nb_norms = jnp.where(nb_mask, norms, -jnp.inf)
    depth = jnp.minimum(trim, jnp.maximum(m_nb - 1, 0))      # (R,)
    # the (k-1-depth)-th order statistic by rank selection (same
    # sort-free trick as the center, one comparison matrix per row)
    n_lt = nb_norms[:, :, None] < nb_norms[:, None, :]
    n_eq = nb_norms[:, :, None] == nb_norms[:, None, :]
    n_ilt = (jnp.arange(k)[:, None] < jnp.arange(k)[None, :])[None]
    n_rank = jnp.sum(n_lt | (n_eq & n_ilt), axis=1)          # (R, K)
    n_sel = n_rank == (k - 1 - depth)[:, None]
    ref = jnp.sum(jnp.where(n_sel, nb_norms, 0.0), axis=1,
                  keepdims=True)
    ref = jnp.where(jnp.isfinite(ref), ref, 0.0)             # (R, 1)
    # the norm gate needs a positive reference (in early sparse rounds a
    # row may see <= trim+1 active neighbors and "3 x 0" would flag the
    # lone honest one) AND a non-aligned payload against a nonzero
    # center (see _TRIM_NORM_ARM_COS) — either false drop would
    # permanently drift the cohort's Lemma-1 invariant
    norm_armed = (ref > 0) & (cnorm[:, None] > 0) \
        & (cos < _TRIM_NORM_ARM_COS)
    flagged = (cos < _TRIM_COS_GATE) | \
              ((norms > _TRIM_NORM_GATE * ref) & norm_armed)  # (R, K)
    flagged = flagged & nb_mask
    return center, flagged


def _clip_scale(vals, mask, self_hot, self_vals, row_ids, clip, dtype):
    """Per-neighbor deviation clipping factors for mode="clip".

    Returns ``(dev, scale, nb_mask)``: the (R, K, d) deviations from self,
    the (R, K) clip factors (``< 1`` exactly where a deviation was actually
    clipped) and the non-self neighborhood mask.
    """
    dev = vals - self_vals[:, None, :]                           # (R, K, d)
    norms = jnp.sqrt(jnp.sum(dev * dev, axis=-1))                # (R, K)
    nb_mask = mask & ~self_hot
    if clip is not None:
        tau = jnp.full(row_ids.shape, clip, dtype)
    else:
        # adaptive threshold: a multiple of the median NEIGHBOR (non-self)
        # deviation norm — same masked-sort machinery on the (R, K) norm
        # rows. The factor leaves typical honest neighbors UNclipped (the
        # aggregation stays exactly linear near consensus, so the Lemma-1
        # invariant drift stops) while a sign-flip payload's ~2||v||
        # deviation still lands far outside it
        m_nb = jnp.sum(nb_mask.astype(jnp.int32), axis=1)
        tau = _masked_trimmed_mean(norms[:, :, None], nb_mask,
                                   (jnp.maximum(m_nb, 1) - 1) // 2,
                                   jnp.maximum(m_nb, 1))[:, 0]
        tau = jnp.where(m_nb > 0, _CLIP_TAU_FACTOR * tau, 0.0)
    scale = jnp.minimum(1.0, tau[:, None] / (norms + 1e-30))     # (R, K)
    return dev, scale, nb_mask


def gate_flags(w_rows: jax.Array, buf: jax.Array, row_ids: jax.Array,
               mode: str, *, trim: int = 1, clip: float | None = None,
               self_override: jax.Array | None = None) -> jax.Array:
    """The (R, K) per-edge rejection mask the robust aggregation applies.

    Same arguments and setup as :func:`robust_neighborhood_mix`; returns
    only the boolean gate decision — True where receiver row r rejects
    sender column k's edge this step (trim/median: the outlier gate fired;
    clip: the deviation was actually clipped). Self slots are never
    flagged. Because every operation mirrors the mix exactly (shared
    helpers), tracing this next to the mix in one jitted program costs
    nothing: XLA CSEs the duplicate subexpressions. This is what the
    ``repro.obs`` telemetry counters sum per sender.
    """
    if mode not in ROBUST_MODES:
        raise ValueError(f"unknown robust mode {mode!r} "
                         f"(want one of {ROBUST_MODES})")
    flat, w_rows, self_hot, mask, counts, self_vals, vals = \
        _neighborhood_setup(w_rows, buf, row_ids, self_override)
    if mode in ("trim", "median"):
        _, flagged = _gate_center_flags(vals, mask, self_hot, counts, trim)
        return flagged
    _, scale, nb_mask = _clip_scale(vals, mask, self_hot, self_vals,
                                    jnp.asarray(row_ids), clip, flat.dtype)
    return (scale < 1.0) & nb_mask


def robust_neighborhood_mix(w_rows: jax.Array, buf: jax.Array,
                            row_ids: jax.Array, mode: str, *,
                            trim: int = 1,
                            clip: float | None = None,
                            self_override: jax.Array | None = None
                            ) -> jax.Array:
    """Robust aggregation of a neighborhood buffer — the Byzantine-resilient
    replacement for ``w_rows @ buf``.

    The mixing-layer defense against participants that lie (PAPERS.md,
    Pasquini et al.): instead of trusting the linear W-contraction, each node
    aggregates its neighborhood with an outlier-suppressing rule. Shared by
    the dense simulator (``robust_mix_dense``: buf is the full stack) and the
    block-plan lowering (``repro.topo.lowering.block_robust_mix_step``: buf
    is the ppermute-assembled zero-filled neighborhood buffer) — every
    computed quantity depends only on slots inside the neighborhood support,
    so the two paths are bitwise identical.

    Args:
      w_rows: (R, K) these nodes' rows of the round's W; the support
        (w != 0, self always included) defines each neighborhood. Under
        churn reweighting a frozen node's row degenerates to e_k and the
        aggregation returns its own value unchanged.
      buf: (K, d_flat) value buffer (rows outside the support may be
        anything — typically zeros in block mode, true values in sim mode).
      row_ids: (R,) global node ids of the rows (``arange(K)`` in sim mode,
        ``device*ln + arange(ln)`` in block mode) — selects each node's own
        value for clipping.
      mode: "trim"   — gated trimmed W-mean: each neighbor is tested
                       against the outlier gate (payload anti-correlated
                       with the neighborhood's coordinate-median center, or
                       norm more than ``_TRIM_NORM_GATE`` x the (trim+1)-th
                       largest neighbor norm); a FLAGGED neighbor's edge is
                       dropped for this step and its weight moved onto the
                       self term; everything else passes through untouched;
            "median" — same outlier gate, but a flagged payload is replaced
                       by the coordinate-wise neighborhood (lower) median
                       instead of dropped, keeping the row weights;
            "clip"   — per-neighbor norm clipping: each neighbor's deviation
                       from the node's own value is clipped to ``clip`` (or,
                       when None, to ``_CLIP_TAU_FACTOR`` x the median
                       neighbor deviation norm), then the usual W-weighted
                       sum runs on clipped values.
      trim: collusion depth the norm gate survives — the inflation
        reference is the (trim+1)-th largest neighbor norm, which ``trim``
        coordinated liars cannot raise.
      self_override: optional (R, ...) HONEST self values — under a wire
        attack (``repro.attack``) ``buf`` holds emitted payloads, but each
        receiving node's own slot is its own state, which was never on the
        wire; the override swaps it in (and the self slot is always exempt
        from the outlier gate — a node trusts itself).

    Why gated instead of an always-on trimmed mean / winsorization: any
    unconditional nonlinearity keeps shaving the K-amplified honest update
    spikes Algorithm 1 emits (v += gamma K dv) — per coordinate an honest
    extreme routinely sits tens of trimmed-window-widths out, so per-
    coordinate statistics cannot tell it from a lie — and the resulting
    mean distortion permanently drifts the Lemma-1 invariant the Prop.-1
    certificate audits: a CLEAN defended run would read as tampered. The
    gate instead decides per NEIGHBOR from whole-vector geometry (honest
    payloads estimate the same dual point, so they correlate positively
    with any robust center and agree in norm; sign-flipped payloads
    anti-correlate and inflated ones stand out in norm), and only flagged
    payloads are rejected. Clean runs therefore take the exact linear path,
    while a stealthy lie that slips the gate must hide inside the honest
    geometry — its per-round influence bounded by what an honest neighbor
    could have said anyway. Breakdown point: the coordinate-median center
    tolerates just under half the neighborhood lying, the norm reference
    ``trim`` colluders; placements where one neighborhood contains several
    coordinated liars (e.g. 2 adjacent Byzantine nodes on tiny graphs) can
    evade or scramble the gate. All modes keep a frozen/self-only
    neighborhood fixed.
    """
    if mode not in ROBUST_MODES:
        raise ValueError(f"unknown robust mode {mode!r} "
                         f"(want one of {ROBUST_MODES})")
    flat, w_rows, self_hot, mask, counts, self_vals, vals = \
        _neighborhood_setup(w_rows, buf, row_ids, self_override)
    r = vals.shape[0]

    if mode in ("trim", "median"):
        center, flagged = _gate_center_flags(vals, mask, self_hot, counts,
                                             trim)
        # NOTE: ``vals`` already carries the self_override substitution
        # (_neighborhood_setup) and ``flagged`` already excludes the self
        # slot (& nb_mask), so neither branch needs a second self-slot
        # where()
        if mode == "median":
            # flagged payloads are replaced outright by the robust center
            clamped = jnp.where(flagged[:, :, None],
                                center[:, None, :], vals)
            out = jnp.einsum("rk,rkd->rd", w_rows,
                             jnp.where(mask[:, :, None], clamped, 0.0),
                             precision=MATMUL)
        else:
            # "trim": drop the flagged edges for this step and move their
            # weight onto the self term — a gated trimmed W-mean. Unlike
            # clamping to a window edge this leaves no residual pull
            # toward the lie's side of the window
            w_eff = jnp.where(flagged, 0.0, w_rows)
            w_drop = jnp.sum(jnp.where(flagged, w_rows, 0.0), axis=1)
            out = jnp.einsum("rk,rkd->rd", w_eff,
                             jnp.where(mask[:, :, None], vals, 0.0),
                             precision=MATMUL)
            out = out + w_drop[:, None] * self_vals
        return out.reshape((r,) + buf.shape[1:])

    # mode == "clip": norm-clip each neighbor's deviation from self
    dev, scale, _ = _clip_scale(vals, mask, self_hot, self_vals,
                                jnp.asarray(row_ids), clip, flat.dtype)
    clipped = self_vals[:, None, :] + dev * scale[:, :, None]
    clipped = jnp.where(mask[:, :, None], clipped, 0.0)
    out = jnp.einsum("rk,rkd->rd", w_rows, clipped, precision=MATMUL)
    return out.reshape((r,) + buf.shape[1:])


def robust_mix_dense(w: jax.Array, v_stack: jax.Array, mode: str, *,
                     trim: int = 1, clip: float | None = None,
                     self_stack: jax.Array | None = None) -> jax.Array:
    """ONE robust gossip step on stacked (K, ...) node state — the dense
    (simulator) counterpart of ``dense_mix`` for ``ColaConfig.robust``.
    ``self_stack`` carries the honest states when ``v_stack`` holds
    attacked wire payloads (see ``robust_neighborhood_mix``)."""
    k = v_stack.shape[0]
    flat = v_stack.reshape(k, -1)
    ov = None if self_stack is None else self_stack.reshape(k, -1)
    out = robust_neighborhood_mix(w, flat, jnp.arange(k), mode,
                                  trim=trim, clip=clip, self_override=ov)
    return out.reshape(v_stack.shape).astype(v_stack.dtype)


def robust_mix_steps(w: jax.Array, v_stack: jax.Array, mode: str, *,
                     trim: int = 1, clip: float | None = None,
                     steps: int = 1,
                     self_stack: jax.Array | None = None) -> jax.Array:
    """B consecutive robust gossip steps. Robust aggregation is nonlinear,
    so there is no W^B fold — the steps apply sequentially (matching the
    on-the-wire ``topo.lowering.block_robust_mix_steps`` exactly). A wire
    attack (``self_stack`` not None) only exists on the FIRST step; later
    steps re-mix already-received values, which are honest."""
    out = v_stack
    for i in range(steps):
        out = robust_mix_dense(w, out, mode, trim=trim, clip=clip,
                               self_stack=self_stack if i == 0 else None)
    return out
