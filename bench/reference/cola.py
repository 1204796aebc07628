"""A plain implementation of CoLA (He, Bian & Jaggi 2018, Algorithm 1).

It imports nothing of the program under test. It follows the paper: K nodes
on a graph with Metropolis-Hastings weights (App. B), the columns of A split
into K equal contiguous blocks (zero-padded), and each round

    v_k    <- sum_l W_kl v_l                                   (gossip)
    dx_[k] <- kappa * n_k cyclic coordinate steps on the local
              subproblem G_k^{sigma'} with sigma' = gamma K     (local solve)
    x_[k]  <- x_[k] + gamma dx_[k]
    v_k    <- v_k + gamma K A_[k] dx_[k]

The local steps use the node's Gram block A_[k]^T A_[k], which in exact
arithmetic equals the residual form of the coordinate update. Prop. 1's
certificate is evaluated as the paper states it: every node's local gap
below eps / (2K), and every node's gradient within
eps (1 - beta) / (2 L sqrt(K) sqrt(sum_k n_k^2 sigma_k)) of its
neighbourhood's (self included) mean gradient, with sigma_k = ||A_[k]||^2
and beta the second largest eigenvalue magnitude of W.

Every contraction goes through ``contract`` at one precision: ``highest``
(float32 products, what the configurations state) or ``high``, three
bfloat16 passes (hi*hi + hi*lo + lo*hi), the nearest precision below,
spelled out so that it computes alike on every backend.
"""
from __future__ import annotations

import importlib.util
import math
from functools import partial
from pathlib import Path

import jax
import jax.numpy as jnp
import numpy as np
from jax import lax

HIGHEST = lax.Precision.HIGHEST
PRECISIONS = ("highest", "high")


def load_problem(name: str):
    """The plain problem module ``bench/reference/<name>.py``."""
    path = Path(__file__).with_name(f"{name}.py")
    if not path.is_file():
        raise ValueError(f"no reference problem {name!r} at {path}")
    spec = importlib.util.spec_from_file_location(f"bench_ref_{name}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _split(x):
    hi = x.astype(jnp.bfloat16).astype(jnp.float32)
    lo = (x - hi).astype(jnp.bfloat16).astype(jnp.float32)
    return hi, lo


def contract(subscripts: str, a, b, precision: str):
    """einsum of two operands at ``precision``."""
    if precision == "highest":
        return jnp.einsum(subscripts, a, b, precision=HIGHEST)
    if precision != "high":
        raise ValueError(f"unknown precision {precision!r}")
    (a_hi, a_lo), (b_hi, b_lo) = _split(a), _split(b)
    e = partial(jnp.einsum, subscripts, precision=HIGHEST)
    return e(a_hi, b_hi) + (e(a_hi, b_lo) + e(a_lo, b_hi))


def graph_adjacency(topology: str, k: int) -> np.ndarray:
    if topology != "ring":
        raise ValueError(f"unknown topology {topology!r}")
    adj = np.zeros((k, k), bool)
    for i in range(k):
        adj[i, (i + 1) % k] = adj[(i + 1) % k, i] = True
    return adj


def metropolis(adj: np.ndarray) -> np.ndarray:
    """W_ij = 1 / (1 + max(deg_i, deg_j)) on edges; the diagonal makes the
    rows sum to one."""
    deg = adj.sum(axis=1)
    w = np.where(adj, 1.0 / (1.0 + np.maximum(deg[:, None], deg[None, :])),
                 0.0)
    return w + np.diag(1.0 - w.sum(axis=1))


class Instance:
    """One problem on K nodes: data, weights and the run-invariant blocks.

    The arrays live in ``ops`` and reach every jitted function as arguments,
    never as constants of its program: ``a`` is A laid out (d, K, n_k), so
    that column j of node k is column k * n_k + j of A, zero past n.
    """

    def __init__(self, a, y, solver: dict, problem: dict, precision: str):
        if precision not in PRECISIONS:
            raise ValueError(f"unknown precision {precision!r}")
        self.prob = load_problem(problem["name"])
        self.params = {k: v for k, v in problem.items() if k != "name"}
        self.precision = precision
        d, n = a.shape
        k = int(solver["nodes"])
        n_k = -(-n // k)
        self.k, self.n, self.n_k, self.d = k, n, n_k, d
        adj = graph_adjacency(solver["topology"], k)
        w = metropolis(adj)
        self.beta = float(np.sort(np.abs(np.linalg.eigvalsh(w)))[-2])
        self.gamma = float(solver.get("gamma", 1.0))
        self.s = self.gamma * k / self.prob.TAU           # sigma' / tau
        self.steps = max(1, int(round(float(solver["kappa"]) * n_k)))
        blocks = _blocks(a, k, n_k)
        self.ops = {
            "a": blocks, "y": y,
            "gram": jax.jit(partial(contract, "dkn,dkm->knm",
                                    precision=precision))(blocks, blocks),
            "mask": (jnp.arange(k * n_k) < n).reshape(k, n_k).astype(
                jnp.float32),
            "w": jnp.asarray(w, jnp.float32),
            "neigh": jnp.asarray(adj | np.eye(k, dtype=bool), jnp.float32),
        }
        self._advance = jax.jit(self._rounds)
        self._certify = jax.jit(self._certificate_terms)
        self._gap = jax.jit(self._gap_terms)

    def _map(self, fn, stack, ops):
        return jax.vmap(fn, (0, None, None))(stack, ops["y"], self.params)

    # -- one round --------------------------------------------------------
    def _cd(self, gram_k, c_k, x_k, m_k):
        q = self.s * jnp.diagonal(gram_k)

        def step(carry, i):
            dx, h = carry
            z = x_k[i] + dx[i]
            grad_i = c_k[i] + self.s * h[i]
            ok = (q[i] > 0) & (m_k[i] > 0)
            size = 1.0 / jnp.where(q[i] > 0, q[i], 1.0)
            z_new = self.prob.prox(z - grad_i * size, size, self.params)
            delta = jnp.where(ok, z_new - z, 0.0)
            return (dx.at[i].add(delta), h + gram_k[:, i] * delta), None

        order = jnp.arange(self.steps) % self.n_k
        zero = jnp.zeros_like(x_k)
        (dx, _), _ = lax.scan(step, (zero, zero), order)
        return dx

    def _round(self, ops, x, v):
        p = self.precision
        v_half = contract("kl,ld->kd", ops["w"], v, p)
        grads = self._map(self.prob.grad_f, v_half, ops)
        c = contract("dkn,kd->kn", ops["a"], grads, p)
        dx = jax.vmap(self._cd)(ops["gram"], c, x, ops["mask"])
        x = x + self.gamma * dx
        v = v_half + self.gamma * self.k * contract(
            "dkn,kn->kd", ops["a"], dx, p)
        return x, v

    def _rounds(self, ops, x, v, count):
        return lax.fori_loop(0, count, lambda _, s: self._round(ops, *s),
                             (x, v))

    def zeros(self):
        return (jnp.zeros((self.k, self.n_k), jnp.float32),
                jnp.zeros((self.k, self.d), jnp.float32))

    def advance(self, x, v, count: int):
        return self._advance(self.ops, x, v, jnp.int32(count))

    # -- what a state says ------------------------------------------------
    def _gap_terms(self, ops, x, v):
        p, prm, mask = self.precision, self.params, ops["mask"]
        w = self._map(self.prob.grad_f, v, ops)
        f_v = self._map(self.prob.f, v, ops)
        fc_w = self._map(self.prob.f_conj, w, ops)
        atw = contract("dkn,d->kn", ops["a"], jnp.mean(w, axis=0), p)
        g_x = jnp.sum(jnp.where(mask > 0, self.prob.g(x, prm), 0.0))
        gc = jnp.sum(mask * self.prob.g_conj(-atw, prm))
        h_a = jnp.mean(f_v) + g_x
        h_b = jnp.mean(fc_w) + gc
        ax = contract("dkn,kn->d", ops["a"], x, p)
        drift = jnp.linalg.norm(jnp.mean(v, axis=0) - ax)
        return jnp.stack([h_a + h_b, drift / (jnp.linalg.norm(ax) + 1.0)])

    def gap(self, x, v) -> dict:
        """The duality gap G_H(x; {v_k}) at w_k = grad f(v_k) and the Lemma-1
        residual ||(1/K) sum_k v_k - A x|| / (||A x|| + 1)."""
        gap, inv = (float(t) for t in self._gap(self.ops, x, v))
        return {"gap": gap, "invariant": inv}

    def _certificate_terms(self, ops, x, v):
        prm, mask, neigh = self.params, ops["mask"], ops["neigh"]
        grads = self._map(self.prob.grad_f, v, ops)
        picked = jnp.where(neigh[:, :, None] > 0, grads[None], 0.0)
        mean = jnp.sum(picked, axis=1) / jnp.sum(neigh, axis=1)[:, None]
        disagree = jnp.linalg.norm(grads - mean, axis=1)
        atg = contract("dkn,kd->kn", ops["a"], grads, self.precision)
        local = jnp.sum(v * grads, axis=1) + jnp.sum(
            mask * (jnp.where(mask > 0, self.prob.g(x, prm), 0.0)
                    + self.prob.g_conj(-atg, prm)), axis=1)
        return local, disagree

    def thresholds(self, eps: float):
        """Prop. 1's right-hand sides (conditions 9 and 10)."""
        gram = np.asarray(self.ops["gram"], np.float64)
        sigma = np.linalg.eigvalsh(gram)[:, -1]
        sizes = np.asarray(self.ops["mask"], np.float64).sum(axis=1)
        bound = self.prob.support_bound(self.params)
        grad = (eps * (1.0 - self.beta)
                / (2.0 * bound * math.sqrt(self.k)
                   * math.sqrt(float(np.sum(sizes ** 2 * sigma)))))
        return eps / (2.0 * self.k), grad

    def certified(self, x, v, thresholds) -> bool:
        local, disagree = self._certify(self.ops, x, v)
        return bool(jnp.all(local <= thresholds[0])
                    & jnp.all(disagree <= thresholds[1]))


@partial(jax.jit, static_argnames=("k", "n_k"))
def _blocks(a, k, n_k):
    d, n = a.shape
    return jnp.pad(a, ((0, 0), (0, k * n_k - n))).reshape(d, k, n_k)


def run(inst: Instance, rounds: int, *, record_every: int = 1,
        eps: float | None = None, keep=()) -> dict:
    """Run up to ``rounds`` rounds. With ``eps``, stop at the first record
    round (t % record_every == 0, or the last) whose state the certificate
    accepts. ``keep``: round counts after which to keep x (as float64).

    Returns {"x", "v", "rounds", "stop_round", "kept"}: the state at the
    certified stop (else where the run ended), how many rounds ran to it,
    the stop round (None without one) and the kept x's by round count.
    """
    thresholds = inst.thresholds(eps) if eps is not None else None
    marks = sorted(set(range(0, rounds, record_every)) | {rounds - 1})
    x, v = inst.zeros()
    done, kept, wanted = 0, {}, set(keep)
    stop = None
    for t in marks:
        for mark in sorted(m for m in wanted if done < m < t + 1):
            # a kept round that falls between record rounds
            x, v = inst.advance(x, v, mark - done)
            done = mark
            kept[mark] = np.asarray(x, np.float64)
        x, v = inst.advance(x, v, t + 1 - done)
        done = t + 1
        if done in wanted:
            kept[done] = np.asarray(x, np.float64)
        if (stop is None and thresholds is not None
                and inst.certified(x, v, thresholds)):
            stop = (t, x, v)
        if stop is not None and wanted <= set(kept):
            break
    if stop is None:
        return {"x": x, "v": v, "rounds": done, "stop_round": None,
                "kept": kept}
    t, x, v = stop
    return {"x": x, "v": v, "rounds": t + 1, "stop_round": t, "kept": kept}
