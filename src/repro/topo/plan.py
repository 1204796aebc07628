"""Topology-program IR: compiled communication plans for arbitrary sparse W.

``compile_plan`` turns the support graph of any (possibly time-varying)
doubly-stochastic mixing matrix into a ``CommPlan``: a greedy edge coloring
of the support into matchings, each matching lowered to one ``lax.ppermute``
permutation (both directions of every edge in one collective). One gossip
step then executes as

    v'_k = W_kk * v_k + sum_c  W[k, partner_c(k)] * recv_c(k)

where ``recv_c`` is the color-c ppermute and the per-node coefficient is
read off the round's W — so a *static* plan (permutations fixed at compile
time) executes *any* reweighting of the support, including churn rounds
where dropped edges simply carry coefficient zero (the ppermute still runs;
the zero multiply discards the payload, and XLA's collective cost is
unchanged). That is what lets the round-block executor keep a single
compiled program across a time-varying graph: the permutations are program
structure, the weights are data.

``PlanSchedule`` materializes the per-round (diag, coefs) pairs into the
executor's stacked ``(T, ...)`` schedule arrays, exactly like the churn
masks; ``plan_mix_dense`` is the mesh-free reference executor used as the
oracle against ``mixing.dense_mix`` in the property tests; the byte
accounting below is what ``launch.dryrun --plan`` renders and what the HLO
assertions in the dist tests budget against.

**Block mode** (``compile_block_plan``): a graph over K paper-nodes also
lowers onto M < K devices (K/M contiguous nodes per device, the runtime's
node-block layout). The node graph is quotiented by the block assignment:
intra-block edges become local (zero-communication) mixing terms, and the
inter-block edges project to a *block-level* multigraph over the M devices
whose parallel edges collapse — one exchange of the whole (K/M, d) block
payload serves every node-pair between two devices. That collapsed device
graph is edge-colored (Misra–Gries, <= Delta_block + 1) so each color is a
matching between devices lowering to one ``lax.ppermute`` of the block
payload. Per-node coefficients are each device's (K/M, K) row slice of the
round's W (``BlockPlanSchedule``), applied as one masked-neighborhood dot
— which is what makes block execution bitwise-equal to the simulator's
dense (K, K) @ (K, d) mix (same contraction, zeros where no exchange
happened and W is zero anyway). The colors-per-step count drops from
O(Delta_node) to O(Delta_block) <= M, the scale lever that runs paper
K=32 sweeps on a 4-device CI mesh.
"""
from __future__ import annotations

import dataclasses
from typing import Tuple

import numpy as np

from repro.core import topology as topo
from repro.core.precision import MATMUL
from repro.topo import coloring

Edge = coloring.Edge


def _payload_bytes(d: int, itemsize: int, wire: str | None,
                   rows: int = 1) -> int:
    """Wire bytes of one ``rows x d`` ppermute payload.

    With ``wire=None`` this is the legacy fp32 accounting (``rows * d *
    itemsize``).  Naming a wire derives the REAL itemsize from the codec
    (1 byte for int8/fp8) and adds the fp32 absmax scale sidecar (one
    scale per node row) — the single source the rendered bytes,
    ``.contract()`` caps and ``comm_budget`` all share, so they cannot
    disagree with each other or with the quantized wire.
    """
    if wire is None:
        return rows * d * itemsize
    from repro.core import quant
    return quant.payload_bytes(d, wire, rows)


def _permutes_per_step(num_colors: int, wire: str | None) -> int:
    """Collective-permutes one gossip step issues: one per color on the
    fp32 wire, two per color on a quantized wire (the int8/fp8 payload and
    its fp32 scale sidecar ppermute as separate collectives)."""
    if wire is None:
        return num_colors
    from repro.core import quant
    return num_colors * (2 if quant.is_quantized(wire) else 1)


def _render_wire_line(plan, d: int, itemsize: int,
                      wire: str | None) -> list:
    """The quantized-wire bytes line ``render`` appends next to the fp32
    figure (empty on fp32/None wires)."""
    from repro.core import quant
    if wire is None or not quant.is_quantized(wire):
        return []
    dev = plan.bytes_per_device_per_step(d, wire=wire)
    dev32 = plan.bytes_per_device_per_step(d, itemsize)
    return [
        f"  wire={wire} (payload {quant.wire_itemsize(wire)} B/elem + "
        f"{quant.SCALE_BYTES} B scale/row): "
        f"per-device<={dev:,} "
        f"per-link={plan.bytes_per_link_per_step(d, wire=wire):,} "
        f"total={plan.total_bytes_per_step(d, wire=wire):,}  "
        f"({dev / dev32:.2f}x fp32)"]


@dataclasses.dataclass(frozen=True)
class CommPlan:
    """A compiled topology program: matchings lowered to ppermute perms.

    Everything here is static host data baked into the compiled round
    program — per-round weights live in ``PlanSchedule``, not here.

    Attributes:
      num_nodes: K.
      colors: per color class, the tuple of undirected edges (i < j).
      perms: per color, the ``lax.ppermute`` (src, dst) pairs — both
        directions of each edge (a matching's swap involution is a valid
        permutation; unmatched nodes send nothing and receive zeros).
      partners: per color, a K-tuple p with p[k] = k's exchange partner in
        that color, or k itself when unmatched (its received payload is
        the ppermute zero-fill and its coefficient is forced to 0).
    """

    num_nodes: int
    colors: Tuple[Tuple[Edge, ...], ...]
    perms: Tuple[Tuple[Tuple[int, int], ...], ...]
    partners: Tuple[Tuple[int, ...], ...]

    @property
    def num_colors(self) -> int:
        return len(self.colors)

    @property
    def num_edges(self) -> int:
        return sum(len(c) for c in self.colors)

    def support(self) -> np.ndarray:
        """(K, K) bool: the off-diagonal exchange pattern this plan covers."""
        s = np.zeros((self.num_nodes, self.num_nodes), dtype=bool)
        for cls in self.colors:
            for i, j in cls:
                s[i, j] = s[j, i] = True
        return s

    def coverage(self) -> np.ndarray:
        """(K, K) bool: the off-diagonal W entries this plan can EXECUTE —
        for a per-node plan, exactly its support (every weight needs a
        permutation to ride)."""
        return self.support()

    def partner_arrays(self) -> np.ndarray:
        """(C, K) int32 partner table (self-index where unmatched)."""
        return np.asarray(self.partners, dtype=np.int32).reshape(
            self.num_colors, self.num_nodes)

    def max_degree(self) -> int:
        return int(self.support().sum(axis=1).max(initial=0))

    def cache_token(self):
        """Hashable identity for compiled-driver cache keys: the program
        structure is exactly the permutations."""
        return ("CommPlan", self.num_nodes, self.colors)

    # -- byte accounting (dryrun --plan, HLO budget assertions) -------------

    def bytes_per_device_per_step(self, d: int, itemsize: int = 4,
                                  wire: str | None = None) -> int:
        """Worst-case per-device ppermute payload of ONE gossip step: one
        (d,)-vector sent per color the node is matched in (<= num_colors).
        ``wire=`` switches to the real wire dtype's accounting (quantized
        elements + scale sidecar); ``itemsize`` is then ignored."""
        return self.num_colors * _payload_bytes(d, itemsize, wire)

    def bytes_per_link_per_step(self, d: int, itemsize: int = 4,
                                wire: str | None = None) -> int:
        """Bytes crossing one graph edge (both directions) per gossip step."""
        return 2 * _payload_bytes(d, itemsize, wire)

    def total_bytes_per_step(self, d: int, itemsize: int = 4,
                             wire: str | None = None) -> int:
        """Network-wide bytes of one gossip step: every edge, both ways."""
        return self.num_edges * self.bytes_per_link_per_step(d, itemsize,
                                                             wire)

    def contract(self, d: int, itemsize: int = 4, *, gossip_steps: int = 1,
                 wire: str | None = None):
        """The declared collective budget of this plan's lowered round
        program (``repro.analysis.contracts.CommContract``): at most
        ``gossip_steps * num_colors`` collective-permutes (twice that on a
        quantized wire — payload + scale sidecar) moving at most
        ``bytes_per_device_per_step`` each step, zero
        all-gathers/all-reduces — what ``analysis.check_comm`` holds the
        compiled HLO to. ``wire='int8'/'fp8'`` derives the cap from the
        quantized payload, so an fp32 payload leaking onto a claimed
        narrow wire overflows the byte clause."""
        from repro.analysis.contracts import CommContract
        from repro.topo.lowering import comm_budget
        budget = comm_budget(self, d, itemsize, gossip_steps=gossip_steps,
                             wire=wire)
        tag = f"-{wire}" if wire else ""
        return CommContract(
            name=f"plan-K{self.num_nodes}-c{self.num_colors}-d{d}{tag}",
            max_collective_permute_count=budget["collective_permutes"],
            max_collective_permute_bytes=budget["bytes_per_device"],
            require_collective_permute=True)

    def render(self, d: int | None = None, itemsize: int = 4,
               max_edges: int = 8, wire: str | None = None) -> str:
        """Human-readable plan (the ``dryrun --plan`` section). Naming a
        quantized ``wire`` adds its per-link/per-device bytes next to the
        fp32 figure."""
        lines = [f"[comm plan] K={self.num_nodes} edges={self.num_edges} "
                 f"colors={self.num_colors} max_degree={self.max_degree()}"]
        for c, cls in enumerate(self.colors):
            shown = ", ".join(f"{i}<->{j}" for i, j in cls[:max_edges])
            more = f", ... +{len(cls) - max_edges}" if len(cls) > max_edges \
                else ""
            lines.append(f"  color {c}: {len(cls)} edge(s)  {shown}{more}")
        if d is not None:
            lines.append(
                f"  bytes/round (1 gossip step, d={d}, itemsize={itemsize}): "
                f"per-device<={self.bytes_per_device_per_step(d, itemsize):,} "
                f"per-link={self.bytes_per_link_per_step(d, itemsize):,} "
                f"total={self.total_bytes_per_step(d, itemsize):,}  "
                f"(dense all-gather per-device="
                f"{self.num_nodes * d * itemsize:,})")
            lines.extend(_render_wire_line(self, d, itemsize, wire))
        return "\n".join(lines)


def _support_adjacency(support) -> np.ndarray:
    """(K, K) adjacency from a Topology or any square matrix's pattern."""
    if isinstance(support, topo.Topology):
        adj = support.adjacency
    else:
        adj = np.asarray(support)
    k = adj.shape[0]
    if adj.ndim != 2 or adj.shape != (k, k):
        raise ValueError(f"support must be square, got {adj.shape}")
    return adj


def _plan_from_classes(classes, k: int, edges) -> CommPlan:
    """Lower validated color classes to ppermute perms + partner tables."""
    coloring.check_coloring(classes, edges, k)
    perms, partners = [], []
    for cls in classes:
        perm = []
        partner = list(range(k))
        for i, j in cls:
            perm.append((i, j))
            perm.append((j, i))
            partner[i], partner[j] = j, i
        perms.append(tuple(sorted(perm)))
        partners.append(tuple(partner))
    return CommPlan(num_nodes=k,
                    colors=tuple(tuple(cls) for cls in classes),
                    perms=tuple(perms), partners=tuple(partners))


def compile_plan(support, *, method: str = "auto") -> CommPlan:
    """Compile a support graph into a ``CommPlan`` (one node per device).

    Args:
      support: a ``core.topology.Topology``, or any (K, K) matrix whose
        off-diagonal nonzero pattern is the exchange graph (a mixing matrix
        works as-is; the diagonal is ignored — self-weights never move
        bytes).
      method: coloring pass (``coloring.edge_coloring``): "auto" never
        exceeds the Vizing bound Delta + 1; "mg" / "greedy" force one pass.
    """
    adj = _support_adjacency(support)
    k = adj.shape[0]
    edges = coloring.undirected_edges(adj)
    classes = coloring.edge_coloring(edges, k, method=method)
    return _plan_from_classes(classes, k, edges)


@dataclasses.dataclass(frozen=True)
class BlockPlan:
    """A ``CommPlan`` over K paper-nodes lowered onto M < K devices.

    Nodes map to devices contiguously (node k lives on device k // (K/M),
    the runtime's node-block layout). The quotient of the node graph by
    that assignment splits the edges:

    * ``intra_edges`` — both endpoints on one device: local mixing terms,
      zero communication;
    * ``inter_edges`` — endpoints on different devices: projected onto the
      block-level multigraph over the M devices, whose parallel edges
      collapse (one (K/M, d) block exchange serves every node-pair between
      the two devices). The collapsed device graph's edge coloring lives in
      ``block`` — a ``CommPlan`` whose "nodes" are the M devices, each
      color one device-matching ppermute of the block payload.
    """

    num_nodes: int            # K paper-nodes
    num_devices: int          # M mesh devices
    block: CommPlan           # device-level plan (block.num_nodes == M)
    intra_edges: Tuple[Edge, ...]  # node-level, both ends on one device
    inter_edges: Tuple[Edge, ...]  # node-level, ends on distinct devices

    @property
    def local_nodes(self) -> int:
        return self.num_nodes // self.num_devices

    @property
    def num_colors(self) -> int:
        """Block-level colors = ppermutes per gossip step."""
        return self.block.num_colors

    @property
    def num_edges(self) -> int:
        """Node-level edge count (intra + inter)."""
        return len(self.intra_edges) + len(self.inter_edges)

    def support(self) -> np.ndarray:
        """(K, K) bool: the NODE-level exchange pattern this plan covers
        (same contract as ``CommPlan.support`` — ``check_plan_covers``
        consumes either)."""
        s = np.zeros((self.num_nodes, self.num_nodes), dtype=bool)
        for i, j in self.intra_edges + self.inter_edges:
            s[i, j] = s[j, i] = True
        return s

    def max_degree(self) -> int:
        return int(self.support().sum(axis=1).max(initial=0))

    def coverage(self) -> np.ndarray:
        """(K, K) bool: the off-diagonal W entries this plan can EXECUTE.

        Wider than ``support()``: EVERY same-device node pair is covered —
        the device's whole block sits in the assembled buffer, so an
        intra-block weight between nodes that were never graph-adjacent
        still computes exactly — plus every node pair whose blocks exchange
        under some color (one block ppermute delivers the full block, not
        just the compiled edges' rows)."""
        k, ln = self.num_nodes, self.local_nodes
        cov = np.zeros((k, k), dtype=bool)
        blocks = [np.arange(b * ln, (b + 1) * ln) for b in
                  range(self.num_devices)]
        for b in range(self.num_devices):
            cov[np.ix_(blocks[b], blocks[b])] = True
        for cls in self.block.colors:
            for u, v in cls:
                cov[np.ix_(blocks[u], blocks[v])] = True
                cov[np.ix_(blocks[v], blocks[u])] = True
        np.fill_diagonal(cov, False)
        return cov

    def device_of(self, node: int) -> int:
        return node // self.local_nodes

    def cache_token(self):
        return ("BlockPlan", self.num_nodes, self.num_devices,
                self.block.cache_token(), self.intra_edges, self.inter_edges)

    # -- byte accounting: per-LINK now means per block-level link -----------

    def bytes_per_device_per_step(self, d: int, itemsize: int = 4,
                                  wire: str | None = None) -> int:
        """Worst-case ppermute payload per device per gossip step: one
        (K/M, d) block per block-level color. ``wire=`` switches to the
        real wire dtype's accounting (quantized elements + one scale per
        node row); ``itemsize`` is then ignored."""
        return self.num_colors * _payload_bytes(d, itemsize, wire,
                                                rows=self.local_nodes)

    def bytes_per_link_per_step(self, d: int, itemsize: int = 4,
                                wire: str | None = None) -> int:
        """Bytes crossing one block-level (device-pair) link, both
        directions — covers ALL node-edges between the two blocks."""
        return 2 * _payload_bytes(d, itemsize, wire, rows=self.local_nodes)

    def total_bytes_per_step(self, d: int, itemsize: int = 4,
                             wire: str | None = None) -> int:
        return self.block.num_edges * self.bytes_per_link_per_step(
            d, itemsize, wire)

    def contract(self, d: int, itemsize: int = 4, *, gossip_steps: int = 1,
                 wire: str | None = None):
        """Block-mode collective budget (see ``CommPlan.contract``): at most
        ``gossip_steps * num_colors`` block-level collective-permutes of
        (K/M, d) payloads per step (twice that on a quantized wire —
        payload + scale sidecar) — ``num_colors <= Delta_block + 1`` by
        the Misra-Gries bound, so this is at least as strict as the Vizing
        budget the dist tests assert."""
        from repro.analysis.contracts import CommContract
        from repro.topo.lowering import comm_budget
        budget = comm_budget(self, d, itemsize, gossip_steps=gossip_steps,
                             wire=wire)
        tag = f"-{wire}" if wire else ""
        return CommContract(
            name=f"block-K{self.num_nodes}-M{self.num_devices}-"
                 f"c{self.num_colors}-d{d}{tag}",
            max_collective_permute_count=budget["collective_permutes"],
            max_collective_permute_bytes=budget["bytes_per_device"],
            require_collective_permute=True)

    def render(self, d: int | None = None, itemsize: int = 4,
               max_edges: int = 8, wire: str | None = None) -> str:
        """Human-readable block plan (the ``dryrun --plan --topo`` section
        when the mesh is smaller than the graph)."""
        ln = self.local_nodes
        lines = [f"[block plan] K={self.num_nodes} nodes on "
                 f"M={self.num_devices} devices ({ln} nodes/device)  "
                 f"edges: intra={len(self.intra_edges)} "
                 f"inter={len(self.inter_edges)} "
                 f"(collapsed to {self.block.num_edges} device link(s))  "
                 f"colors={self.num_colors}"]
        for c, cls in enumerate(self.block.colors):
            shown = ", ".join(f"dev{i}<->dev{j}" for i, j in cls[:max_edges])
            more = f", ... +{len(cls) - max_edges}" if len(cls) > max_edges \
                else ""
            lines.append(f"  color {c}: {len(cls)} link(s)  {shown}{more}")
        if d is not None:
            lines.append(
                f"  bytes/round (1 gossip step, d={d}, itemsize={itemsize}): "
                f"per-device<={self.bytes_per_device_per_step(d, itemsize):,} "
                f"per-link={self.bytes_per_link_per_step(d, itemsize):,} "
                f"total={self.total_bytes_per_step(d, itemsize):,}  "
                f"(dense all-gather per-device="
                f"{self.num_nodes * d * itemsize:,})")
            lines.extend(_render_wire_line(self, d, itemsize, wire))
        return "\n".join(lines)


def compile_block_plan(support, num_devices: int, *,
                       method: str = "auto") -> BlockPlan:
    """Quotient a K-node support graph onto M devices and color the result.

    Args:
      support: as ``compile_plan`` (Topology or (K, K) pattern).
      num_devices: M; K % M == 0, K/M contiguous nodes per device.
      method: coloring pass for the collapsed device graph (see
        ``coloring.edge_coloring``; "auto" <= Delta_block + 1).
    """
    adj = _support_adjacency(support)
    k = adj.shape[0]
    if num_devices < 1 or k % num_devices != 0:
        raise ValueError(f"K={k} nodes must divide over M={num_devices} "
                         "devices (contiguous node blocks)")
    ln = k // num_devices
    intra, inter = [], []
    block_adj = np.zeros((num_devices, num_devices), dtype=bool)
    for i, j in coloring.undirected_edges(adj):
        bi, bj = i // ln, j // ln
        if bi == bj:
            intra.append((i, j))
        else:
            inter.append((i, j))
            block_adj[bi, bj] = block_adj[bj, bi] = True
    block_edges = coloring.undirected_edges(block_adj)
    classes = coloring.edge_coloring(block_edges, num_devices, method=method)
    return BlockPlan(num_nodes=k, num_devices=num_devices,
                     block=_plan_from_classes(classes, num_devices,
                                              block_edges),
                     intra_edges=tuple(intra), inter_edges=tuple(inter))


def check_plan_covers(plan: CommPlan, w: np.ndarray,
                      atol: float = 0.0) -> None:
    """Raise ValueError if ``w`` has off-diagonal mass outside the plan.

    The generalization of ``mixing.check_circulant_band``: plan execution
    reproduces ``dense_mix(w, .)`` exactly iff every nonzero off-diagonal
    W_ij rides some color's permutation. Churn-reweighted matrices over the
    compiled graph always pass (reweighting only *removes* edges); a
    w_override with extra edges must recompile. Accepts a ``CommPlan`` or a
    ``BlockPlan`` — both expose ``coverage()``, the executable pattern this
    checks (for a block plan that is wider than the compiled graph edges:
    intra-block entries ride the local mixing term and any pair of
    exchanging blocks rides the full block payload).
    """
    w = np.asarray(w)
    if w.shape != (plan.num_nodes, plan.num_nodes):
        raise ValueError(f"W shape {w.shape} does not match the plan's "
                         f"K={plan.num_nodes}")
    off = np.abs(w.copy())
    np.fill_diagonal(off, 0.0)
    uncovered = off * ~plan.coverage()
    if uncovered.max(initial=0.0) > atol:
        i, j = np.unravel_index(np.argmax(uncovered), uncovered.shape)
        raise ValueError(
            f"W[{i},{j}]={w[i, j]:.3g} lies outside the compiled plan's "
            f"support — plan execution would drop that weight mass; "
            "recompile the plan from this W's support (or use the dense "
            "mixing path)")


def plan_coefficients(plan: CommPlan, w, *, check: bool = True
                      ) -> tuple[np.ndarray, np.ndarray]:
    """(diag (K,), coefs (C, K)) for one round's mixing matrix ``w``.

    ``diag[k] = W_kk``; ``coefs[c, k] = W[k, partner_c(k)]`` (0 where
    unmatched). Together with the plan's permutations these reproduce
    ``dense_mix(w, v)``: every off-diagonal entry appears in exactly one
    color, the diagonal in the local term.
    """
    w = np.asarray(w)
    if check:
        check_plan_covers(plan, w)
    k = plan.num_nodes
    diag = np.ascontiguousarray(np.diag(w))
    coefs = np.zeros((plan.num_colors, k), dtype=w.dtype)
    rows = np.arange(k)
    for c, partner in enumerate(plan.partner_arrays()):
        matched = partner != rows
        coefs[c, matched] = w[rows[matched], partner[matched]]
    return diag, coefs


def w_from_coefficients(plan: CommPlan, diag, coefs) -> np.ndarray:
    """Reassemble the (K, K) mixing matrix from one round's plan entries.

    Exact inverse of ``plan_coefficients`` over the plan's support: the
    diagonal comes back from ``diag``, and each color's coefficient row
    scatters to ``W[k, partner_c(k)]`` (unmatched slots carry 0 and stay
    off the matrix). Consumers that only see the lowered schedule —
    telemetry on the per-node CommPlan dist path reconstructs W from
    ``plan_diag``/``plan_coefs`` this way — get the same matrix the round
    actually mixed with, because every executable off-diagonal entry lives
    in exactly one color.
    """
    diag = np.asarray(diag)
    coefs = np.asarray(coefs)
    k = plan.num_nodes
    if diag.shape != (k,):
        raise ValueError(f"diag shape {diag.shape} != ({k},)")
    if coefs.shape != (plan.num_colors, k):
        raise ValueError(
            f"coefs shape {coefs.shape} != ({plan.num_colors}, {k})")
    w = np.zeros((k, k), dtype=np.result_type(diag.dtype, coefs.dtype))
    np.fill_diagonal(w, diag)
    rows = np.arange(k)
    for c, partner in enumerate(plan.partner_arrays()):
        matched = partner != rows
        w[rows[matched], partner[matched]] = coefs[c, matched]
    return w


def w_from_coefficients_device(plan: CommPlan, diag, coefs):
    """``w_from_coefficients`` for traced (on-device) schedule slices.

    Same scatter driven by the plan's static partner tables, built with
    ``jnp`` so it can run inside the dist runtime's jitted round step —
    this is how telemetry on the per-node CommPlan path recovers the round
    W the executed coefficients encode (the (T, K, K) stack was dropped
    from the device schedule at lowering time).
    """
    import jax.numpy as jnp

    k = plan.num_nodes
    diag = jnp.asarray(diag)
    rows = np.arange(k)
    w = jnp.zeros((k, k), dtype=diag.dtype)
    w = w.at[rows, rows].set(diag)
    for c, partner in enumerate(plan.partner_arrays()):
        matched = partner != rows
        w = w.at[rows[matched], partner[matched]].set(coefs[c][matched])
    return w


@dataclasses.dataclass(frozen=True)
class PlanSchedule:
    """Per-round plan coefficients, materialized like the churn masks.

    ``diag`` (T, K) and ``coefs`` (T, C, K) are stacked schedule arrays the
    round-block executor slices per block; a static (round-invariant) W
    yields broadcast views, O(C*K) host memory regardless of T.
    """

    diag: np.ndarray   # (T, K)
    coefs: np.ndarray  # (T, C, K)

    @classmethod
    def from_w_stack(cls, plan: CommPlan, w_stack, *,
                     static: bool = False) -> "PlanSchedule":
        """Compile every round's coefficients (validating coverage per
        round). ``static=True`` asserts the stack is round-invariant and
        stores broadcast views instead of T copies."""
        w_stack = np.asarray(w_stack)
        t = w_stack.shape[0]
        if static or t == 0:
            w0 = w_stack[0] if t else np.eye(plan.num_nodes)
            if t and not (w_stack == w0).all():
                raise ValueError(
                    "PlanSchedule.from_w_stack(static=True) requires a "
                    "round-invariant W stack — this one varies; drop "
                    "static= to materialize per-round coefficients")
            diag0, coefs0 = plan_coefficients(plan, w0)
            return cls(
                diag=np.broadcast_to(diag0.astype(w_stack.dtype),
                                     (t,) + diag0.shape),
                coefs=np.broadcast_to(coefs0.astype(w_stack.dtype),
                                      (t,) + coefs0.shape))
        diag = np.empty((t, plan.num_nodes), dtype=w_stack.dtype)
        coefs = np.empty((t, plan.num_colors, plan.num_nodes),
                         dtype=w_stack.dtype)
        for t_i in range(t):
            diag[t_i], coefs[t_i] = plan_coefficients(plan, w_stack[t_i])
        return cls(diag=diag, coefs=coefs)

    def entries(self) -> dict:
        """The executor schedule entries the dist runtime splices in."""
        return {"plan_diag": self.diag, "plan_coefs": self.coefs}


def plan_mix_dense(plan: CommPlan, diag, coefs, v_stack):
    """Mesh-free reference executor: apply one plan-compiled gossip step to
    stacked (K, ...) state with jnp gathers standing in for the ppermutes.

    This is the oracle the property tests pin against ``mixing.dense_mix``
    (equal to float tolerance — the color-by-color summation order differs
    from the matmul's) and the program the shard_map lowering
    (``repro.topo.lowering.plan_mix_step``) must match shard-for-shard.
    """
    import jax.numpy as jnp

    v_stack = jnp.asarray(v_stack)
    flat = v_stack.reshape(v_stack.shape[0], -1)
    diag = jnp.asarray(diag, dtype=flat.dtype)
    coefs = jnp.asarray(coefs, dtype=flat.dtype)
    out = diag[:, None] * flat
    for c, partner in enumerate(plan.partner_arrays()):
        out = out + coefs[c][:, None] * flat[partner]
    return out.reshape(v_stack.shape)


def mix_with_plan(plan: CommPlan, w, v_stack):
    """Convenience: one gossip step of ``w`` through the compiled plan."""
    diag, coefs = plan_coefficients(plan, w)
    return plan_mix_dense(plan, diag, coefs, v_stack)


# ---------------------------------------------------------------------------
# block mode: K nodes on M devices
# ---------------------------------------------------------------------------

@dataclasses.dataclass(frozen=True)
class BlockPlanSchedule:
    """Per-round mixing matrices for block-mode plan execution.

    Block mode's per-node coefficients ARE each device's (K/M, K) row slice
    of the round's W — the coefficient mask that weights the device's
    assembled neighborhood buffer in one dot (``lowering.block_mix_step``).
    ``w`` is the (T, K, K) round stack the dist runtime shards row-wise
    over the node axis per round; coverage against the compiled plan is
    validated here (per round, or once for a ``static`` stack stored as
    broadcast views), so a round whose W grew edges outside the compiled
    support fails loudly instead of silently dropping weight mass.
    """

    w: np.ndarray  # (T, K, K)

    @classmethod
    def from_w_stack(cls, plan: BlockPlan, w_stack, *,
                     static: bool = False) -> "BlockPlanSchedule":
        w_stack = np.asarray(w_stack)
        t = w_stack.shape[0]
        if static or t == 0:
            w0 = w_stack[0] if t else np.eye(plan.num_nodes,
                                             dtype=w_stack.dtype)
            if t and not (w_stack == w0).all():
                raise ValueError(
                    "BlockPlanSchedule.from_w_stack(static=True) requires a "
                    "round-invariant W stack — this one varies; drop "
                    "static= to validate per-round coverage")
            check_plan_covers(plan, w0)
            return cls(w=np.broadcast_to(w0, (t,) + w0.shape))
        for t_i in range(t):
            check_plan_covers(plan, w_stack[t_i])
        return cls(w=w_stack)

    def entries(self) -> dict:
        """The executor schedule entry the dist runtime splices in (sharded
        ``P(axis)`` on the row dimension of each round's slice)."""
        return {"plan_w": self.w}


def block_mix_dense(plan: BlockPlan, w, v_stack, *, check: bool = True):
    """Mesh-free reference executor for block mode: per device, assemble
    the (K, d) neighborhood buffer (own block + one block per block-level
    color; never-exchanged blocks stay zero) and apply the device's (K/M, K)
    W rows in ONE dot.

    Because every nonzero W entry lands on an assembled row (coverage
    checked) and assembled-but-unweighted rows multiply exact zeros, each
    device's dot runs the same contraction as the dense (K, K) @ (K, d)
    matmul — ``mixing.dense_mix`` BITWISE, which is the parity contract the
    distributed block lowering (``lowering.block_mix_step``) is pinned to.
    """
    import jax.numpy as jnp

    w = np.asarray(w)
    if check:
        check_plan_covers(plan, w)
    k, m, ln = plan.num_nodes, plan.num_devices, plan.local_nodes
    v_stack = jnp.asarray(v_stack)
    flat = v_stack.reshape(k, -1)
    partners = plan.block.partner_arrays()  # (C, M)
    outs = []
    for dev in range(m):
        buf = jnp.zeros_like(flat)
        buf = buf.at[dev * ln:(dev + 1) * ln].set(
            flat[dev * ln:(dev + 1) * ln])
        for c in range(plan.num_colors):
            src = int(partners[c, dev])
            if src != dev:
                buf = buf.at[src * ln:(src + 1) * ln].set(
                    flat[src * ln:(src + 1) * ln])
        w_rows = jnp.asarray(w[dev * ln:(dev + 1) * ln], dtype=flat.dtype)
        outs.append(jnp.matmul(w_rows, buf, precision=MATMUL))
    return jnp.concatenate(outs, axis=0).reshape(v_stack.shape)


def mix_with_block_plan(plan: BlockPlan, w, v_stack):
    """Convenience: one gossip step of ``w`` through the block plan."""
    return block_mix_dense(plan, w, v_stack)


def block_robust_mix_dense(plan: BlockPlan, w, v_stack, mode: str, *,
                           trim: int = 1, clip: float | None = None,
                           check: bool = True, self_stack=None):
    """Mesh-free reference executor for ROBUST block mode: per device,
    assemble the zero-filled neighborhood buffer exactly as
    ``block_mix_dense`` does, then aggregate the device's node rows with
    ``repro.core.mixing.robust_neighborhood_mix`` instead of the dot.

    The robust rule reads only buffer slots inside each row's W support
    (coverage-checked), so this equals the full-stack
    ``mixing.robust_mix_dense`` BITWISE — the parity contract the shard_map
    robust lowering (``lowering.block_robust_mix_step``) is pinned to.

    ``self_stack`` (K, ...) supplies honest per-node states overriding each
    node's OWN buffer slot when ``v_stack`` is an attacked wire payload.
    """
    import jax.numpy as jnp

    from repro.core import mixing as core_mixing

    w = np.asarray(w)
    if check:
        check_plan_covers(plan, w)
    k, m, ln = plan.num_nodes, plan.num_devices, plan.local_nodes
    v_stack = jnp.asarray(v_stack)
    flat = v_stack.reshape(k, -1)
    partners = plan.block.partner_arrays()  # (C, M)
    outs = []
    for dev in range(m):
        buf = jnp.zeros_like(flat)
        buf = buf.at[dev * ln:(dev + 1) * ln].set(
            flat[dev * ln:(dev + 1) * ln])
        for c in range(plan.num_colors):
            src = int(partners[c, dev])
            if src != dev:
                buf = buf.at[src * ln:(src + 1) * ln].set(
                    flat[src * ln:(src + 1) * ln])
        w_rows = jnp.asarray(w[dev * ln:(dev + 1) * ln], dtype=flat.dtype)
        row_ids = jnp.arange(dev * ln, (dev + 1) * ln)
        ov = None
        if self_stack is not None:
            ov = jnp.asarray(self_stack).reshape(k, -1)[dev * ln:(dev + 1) * ln]
        outs.append(core_mixing.robust_neighborhood_mix(
            w_rows, buf, row_ids, mode, trim=trim, clip=clip,
            self_override=ov))
    out = jnp.concatenate(outs, axis=0)
    return out.reshape(v_stack.shape).astype(v_stack.dtype)
