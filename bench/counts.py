"""Floating-point operations and HBM bytes that CoLA's work needs, from shapes.

Counts are of the algorithm, not of how a program lays it out: a matrix
product of (m, k) by (k, n) is 2 m k n operations, and an operand read from
HBM is counted once per pass that the algorithm cannot avoid. All arrays
are float32 (4 bytes). Shapes: A is d x n, split over K nodes into blocks
of n_k = ceil(n / K) columns (zero-padded); kappa * n_k coordinate steps
per node per round.

Elementwise work is counted at a few operations per element, and the small
arrays (x, W, the Gram blocks when they stay in fast memory) at one pass;
both are far below the passes over A in every configuration here.
"""
from __future__ import annotations

import dataclasses
import json
import math
from pathlib import Path

F32 = 4
PEAKS_FILE = Path(__file__).with_name("peaks.json")
# operations per element of grad f: lasso v - y; logistic -y / (1 + e^{yv})
GRAD_OPS = {"lasso": 1, "logistic_l2": 4}


@dataclasses.dataclass(frozen=True)
class Cost:
    flops: float = 0.0
    bytes: float = 0.0

    def __add__(self, other: "Cost") -> "Cost":
        return Cost(self.flops + other.flops, self.bytes + other.bytes)

    def __mul__(self, times: float) -> "Cost":
        return Cost(self.flops * times, self.bytes * times)

    __rmul__ = __mul__


@dataclasses.dataclass(frozen=True)
class Shape:
    d: int                 # samples: rows of A
    n: int                 # features: columns of A
    k: int                 # nodes
    kappa: float
    problem: str           # "lasso" | "logistic_l2"
    cd_path: str = "gram"  # "gram" | "residual"

    @property
    def n_k(self) -> int:
        return -(-self.n // self.k)

    @property
    def steps(self) -> int:
        return max(1, int(round(self.kappa * self.n_k)))

    @property
    def blocks_bytes(self) -> int:
        """a_parts, (K, d, n_k)."""
        return self.k * self.d * self.n_k * F32

    @classmethod
    def of(cls, config: dict) -> "Shape":
        data, solver = config["data"], config["solver"]
        return cls(d=int(data["samples"]), n=int(data["features"]),
                   k=int(solver["nodes"]), kappa=float(solver["kappa"]),
                   problem=config["problem"]["name"],
                   cd_path=solver.get("cd_path", "gram"))


def mixing(s: Shape) -> Cost:
    """v <- W v: (K, K) @ (K, d) is 2 K^2 d operations; v is read and the
    mixed v written once, 2 K d floats."""
    return Cost(2 * s.k * s.k * s.d, 2 * s.k * s.d * F32)


def gradients(s: Shape) -> Cost:
    """grad f at each node's mixed v: GRAD_OPS per element of (K, d), fused
    with the mixing's output (no bytes of its own)."""
    return Cost(GRAD_OPS[s.problem] * s.k * s.d, 0)


def local_solve(s: Shape) -> Cost:
    """kappa n_k coordinate steps on each of K nodes.

    Gram path: c = A_[k]^T grad (2 K d n_k operations, one pass over
    a_parts), then per step grad_i = c_i + (sigma'/tau) h_i, the prox and
    h += G[:, i] delta: 2 n_k + 10 operations; the (n_k, n_k) Gram blocks
    are read once, K n_k^2 floats.
    Residual path: per step a column dot and an axpy over d, 4 d + 10
    operations; the columns are read once per pass over the block.
    """
    if s.cd_path == "gram":
        return Cost(2 * s.k * s.d * s.n_k + s.k * s.steps * (2 * s.n_k + 10),
                    s.blocks_bytes + s.k * s.n_k * s.n_k * F32)
    passes = math.ceil(s.steps / s.n_k)
    return Cost(s.k * s.steps * (4 * s.d + 10), passes * s.blocks_bytes)


def local_update(s: Shape) -> Cost:
    """dv = A_[k] dx_[k]: 2 K d n_k operations, one pass over a_parts;
    v <- v + gamma K dv: 2 K d operations, v read and written (2 K d
    floats)."""
    return Cost(2 * s.k * s.d * s.n_k + 2 * s.k * s.d,
                s.blocks_bytes + 2 * s.k * s.d * F32)


def round_cost(s: Shape) -> Cost:
    """One round of Algorithm 1. On the Gram path it makes two passes over
    a_parts: for the lasso of Fig. 1 (d=10,000, n=1,000, K=16, kappa=8)
    80.6 MB of the round's 83.5 MB, and 47.0 MFLOP."""
    return mixing(s) + gradients(s) + local_solve(s) + local_update(s)


def gap_record(s: Shape) -> Cost:
    """The Lemma-2 duality gap at w_k = grad f(v_k): A x and A^T w_bar are
    two passes over A (4 d n operations, 2 d n floats); grad f, f, f* and
    the consensus violation over the (K, d) stack are ~12 K d operations
    and one read of v."""
    return Cost(4 * s.d * s.n + 12 * s.k * s.d,
                2 * s.d * s.n * F32 + s.k * s.d * F32)


def certificate_record(s: Shape) -> Cost:
    """Prop. 1's local certificates: A_[k]^T grad f(v_k) for condition 9
    and sum_k A_[k] x_[k] for the Lemma-1 residual are two passes over
    a_parts (4 K d n_k operations); gradients, the neighbourhood mean over
    K neighbours and the norms are ~(K + 8) K d operations and one read of
    v."""
    return Cost(4 * s.k * s.d * s.n_k + (s.k + 8) * s.k * s.d,
                2 * s.blocks_bytes + s.k * s.d * F32)


RECORDS = {"gap": gap_record, "certificate": certificate_record}


def record_cost(s: Shape, recorder: str) -> Cost:
    """A record round's extra work; ``recorder`` is "gap", "certificate" or
    "gap+certificate"."""
    total = Cost()
    for part in recorder.split("+"):
        total = total + RECORDS[part](s)
    return total


def env_build(s: Shape) -> Cost:
    """Per solve: A split into a_parts (read A, write a_parts: 2 d K n_k
    floats) and, on the Gram path, the Gram blocks A_[k]^T A_[k]
    (2 K d n_k^2 operations, one more pass over a_parts)."""
    split = Cost(0, 2 * s.blocks_bytes)
    if s.cd_path != "gram":
        return split
    return split + Cost(2 * s.k * s.d * s.n_k ** 2, s.blocks_bytes)


def window_rounds(s: Shape, rounds: int, records: int,
                  recorder: str) -> Cost:
    """The rounds of a window: ``rounds`` rounds, ``records`` of them record
    rounds."""
    return rounds * round_cost(s) + records * record_cost(s, recorder)


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip of ``device_kind`` (``peaks.json``).
    A kind that is not in the table is an error."""
    table = json.loads(PEAKS_FILE.read_text())["kinds"]
    if device_kind not in table:
        raise ValueError(f"no published peaks for device kind "
                         f"{device_kind!r} (known: {sorted(table)})")
    return table[device_kind]


def roofline_seconds(cost: Cost, peak: dict, chips: int) -> tuple:
    """(least seconds, bound) of ``cost`` spread over ``chips`` chips: the
    larger of operations over the bf16 peak and bytes over HBM bandwidth;
    ``bound`` names which."""
    t_flops = cost.flops / (chips * peak["flops_bf16"])
    t_bytes = cost.bytes / (chips * peak["hbm_bytes_per_s"])
    return (t_bytes, "bytes") if t_bytes >= t_flops else (t_flops, "flops")
