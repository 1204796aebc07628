"""GLM problem definitions mapped to the CoLA primal/dual pair (A)/(B).

Problem (A):  min_x  f(A x) + sum_i g_i(x_i),  A in R^{d x n}, columns A_i.

Every problem supplies:
  * ``f``, ``grad_f`` and the smoothness constant ``1/tau`` (f is (1/tau)-smooth),
  * the convex conjugate ``f_conj`` (for duality gaps, Lemma 2),
  * separable ``g`` via elementwise ``g_el(x, p)`` / ``g_conj_el(u, p)`` where
    ``p`` is an optional per-coordinate parameter vector (e.g. the labels in the
    sample-partitioned ridge-dual mapping) that is partitioned across nodes
    together with the columns of A,
  * the proximal operator ``prox_g_el(z, step, p)``,
  * strong convexity ``mu_g`` (Thm 1) and support bound ``l_bound`` (Thm 2).

Mappings follow Duenner et al. 2016 / Smith et al. 2018 (CoCoA), which the
paper builds on. L1 problems use the standard B-bounded-support modification so
Theorem 2's L-bounded-support assumption holds and duality gaps are finite.
"""
from __future__ import annotations

import dataclasses
from typing import Callable

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.precision import MATMUL


@dataclasses.dataclass(frozen=True)
class Problem:
    """A composite objective f(Ax) + sum_i g_i(x_i) with its dual structure."""

    name: str
    a: jax.Array  # data matrix, (d, n)
    f: Callable[[jax.Array], jax.Array]
    grad_f: Callable[[jax.Array], jax.Array]
    f_conj: Callable[[jax.Array], jax.Array]
    g_el: Callable[[jax.Array, jax.Array], jax.Array]
    g_conj_el: Callable[[jax.Array, jax.Array], jax.Array]
    prox_g_el: Callable[[jax.Array, jax.Array, jax.Array], jax.Array]
    tau: float          # f is (1/tau)-smooth
    mu_g: float         # strong convexity of every g_i
    l_bound: float      # L-bounded support of g_i (inf if not bounded)
    g_param: jax.Array | None = None  # (n,) per-coordinate parameter or None
    # (l1, l2, box) of the generalized elastic-net prox family
    #   prox(z) = clip(soft(z - step*g_param_i, step*l1) / (1 + step*l2), +-box)
    # — consumed by the Pallas CD kernel (repro.kernels.cd_glm).
    prox_spec: tuple = (0.0, 0.0, np.inf)

    @property
    def d(self) -> int:
        return self.a.shape[0]

    @property
    def n(self) -> int:
        return self.a.shape[1]

    def g_params(self) -> jax.Array:
        if self.g_param is None:
            return jnp.zeros((self.n,), dtype=self.a.dtype)
        return self.g_param

    def g(self, x: jax.Array) -> jax.Array:
        return jnp.sum(self.g_el(x, self.g_params()))

    def objective(self, x: jax.Array) -> jax.Array:
        """F_A(x) = f(Ax) + g(x)."""
        return self.f(jnp.matmul(self.a, x, precision=MATMUL)) + self.g(x)

    def dual_objective(self, w: jax.Array) -> jax.Array:
        """F_B(w) = f*(w) + sum_i g_i*(-A_i^T w)  (problem (B))."""
        atw = jnp.matmul(self.a.T, w, precision=MATMUL)
        return self.f_conj(w) + jnp.sum(self.g_conj_el(-atw, self.g_params()))


# ---------------------------------------------------------------------------
# f parts (data-fit terms)
# ---------------------------------------------------------------------------

def _quadratic_f(b: jax.Array):
    """f(v) = 0.5 ||v - b||^2  -> 1-smooth (tau = 1); f*(w) = 0.5||w||^2 + <w, b>."""
    def f(v):
        return 0.5 * jnp.sum((v - b) ** 2)

    def grad_f(v):
        return v - b

    def f_conj(w):
        return 0.5 * jnp.sum(w ** 2) + jnp.dot(w, b)

    return f, grad_f, f_conj, 1.0


def _logistic_f(y: jax.Array):
    """f(v) = sum_j log(1 + exp(-y_j v_j)); (1/4)-smooth -> tau = 4.

    f*(w): with u := -w.y constrained to [0,1],
    f*(w) = sum_j u log u + (1-u) log(1-u)  (negative binary entropy).
    """
    def f(v):
        return jnp.sum(jnp.logaddexp(0.0, -y * v))

    def grad_f(v):
        return -y * jax.nn.sigmoid(-y * v)

    def f_conj(w):
        u = jnp.clip(-w * y, 1e-12, 1.0 - 1e-12)
        return jnp.sum(u * jnp.log(u) + (1.0 - u) * jnp.log1p(-u))

    return f, grad_f, f_conj, 4.0


# ---------------------------------------------------------------------------
# g parts (separable terms). All take (x, p) with p an unused-or-used
# per-coordinate parameter so that they vectorize over partitioned blocks.
# ---------------------------------------------------------------------------

def _l2_g(lam: float):
    def g_el(x, p):
        return 0.5 * lam * x ** 2

    def g_conj_el(u, p):
        return u ** 2 / (2.0 * lam)

    def prox(z, step, p):
        return z / (1.0 + step * lam)

    return g_el, g_conj_el, prox, lam, np.inf


def _l1_g(lam: float, box: float):
    """g_i(x) = lam |x| + i{|x| <= box}; g*(u) = box * max(0, |u| - lam)."""
    def g_el(x, p):
        return lam * jnp.abs(x) + jnp.where(jnp.abs(x) <= box, 0.0, jnp.inf)

    def g_conj_el(u, p):
        return box * jnp.maximum(0.0, jnp.abs(u) - lam)

    def prox(z, step, p):
        soft = jnp.sign(z) * jnp.maximum(jnp.abs(z) - step * lam, 0.0)
        return jnp.clip(soft, -box, box)

    return g_el, g_conj_el, prox, 0.0, box


def _elastic_net_g(lam: float, alpha: float, box: float):
    """g_i(x) = lam * (alpha |x| + (1-alpha)/2 x^2)."""
    l1 = lam * alpha
    l2 = lam * (1.0 - alpha)

    def g_el(x, p):
        return l1 * jnp.abs(x) + 0.5 * l2 * x ** 2

    def g_conj_el(u, p):
        if l2 > 0:
            return jnp.maximum(0.0, jnp.abs(u) - l1) ** 2 / (2.0 * l2)
        return box * jnp.maximum(0.0, jnp.abs(u) - l1)

    def prox(z, step, p):
        soft = jnp.sign(z) * jnp.maximum(jnp.abs(z) - step * l1, 0.0)
        return soft / (1.0 + step * l2)

    l_bound = np.inf if l2 > 0 else box
    return g_el, g_conj_el, prox, l2, l_bound


# ---------------------------------------------------------------------------
# Problem constructors
# ---------------------------------------------------------------------------

def ridge_primal(x_data: jax.Array, y: jax.Array, lam: float) -> Problem:
    """Ridge regression, feature-partitioned: min_x 0.5||Xx-y||^2 + lam/2||x||^2."""
    f, grad_f, f_conj, tau = _quadratic_f(y)
    g_el, g_conj_el, prox, mu, l = _l2_g(lam)
    return Problem("ridge_primal", x_data, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, tau, mu, l,
                   prox_spec=(0.0, lam, np.inf))


def ridge_dual(x_data: jax.Array, y: jax.Array, lam: float) -> Problem:
    """Ridge regression mapped through (B): sample-partitioned.

    With f(v)=0.5||v-y||^2 and g=lam/2||.||^2, problem (B) over w (one dual
    variable per sample) is  min_w 0.5||w||^2 + <w,y> + ||X^T w||^2/(2 lam),
    itself of form (A) with A~ = X^T (columns = samples),
    f~(u) = ||u||^2/(2 lam) and g~_j(w_j) = 0.5 w_j^2 + y_j w_j.
    """
    at = x_data.T  # (n_features, n_samples): columns are samples

    def f(u):
        return jnp.sum(u ** 2) / (2.0 * lam)

    def grad_f(u):
        return u / lam

    def f_conj(s):
        return 0.5 * lam * jnp.sum(s ** 2)

    def g_el(w, p):
        return 0.5 * w ** 2 + p * w

    def g_conj_el(u, p):
        return 0.5 * (u - p) ** 2

    def prox(z, step, p):
        return (z - step * p) / (1.0 + step)

    return Problem("ridge_dual", at, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, lam, 1.0, np.inf, g_param=y,
                   prox_spec=(0.0, 1.0, np.inf))


def lasso(x_data: jax.Array, y: jax.Array, lam: float, box: float = 10.0) -> Problem:
    """Lasso, feature-partitioned: min_x 0.5||Xx - y||^2 + lam ||x||_1."""
    f, grad_f, f_conj, tau = _quadratic_f(y)
    g_el, g_conj_el, prox, mu, l = _l1_g(lam, box)
    return Problem("lasso", x_data, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, tau, mu, l,
                   prox_spec=(lam, 0.0, box))


def elastic_net(x_data: jax.Array, y: jax.Array, lam: float, alpha: float = 0.5,
                box: float = 1e3) -> Problem:
    f, grad_f, f_conj, tau = _quadratic_f(y)
    g_el, g_conj_el, prox, mu, l = _elastic_net_g(lam, alpha, box)
    return Problem("elastic_net", x_data, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, tau, mu, l,
                   prox_spec=(lam * alpha, lam * (1.0 - alpha), box))


def logistic_l2(x_data: jax.Array, y: jax.Array, lam: float) -> Problem:
    """L2-regularized logistic regression, feature-partitioned. y in {-1, +1}."""
    f, grad_f, f_conj, tau = _logistic_f(y)
    g_el, g_conj_el, prox, mu, l = _l2_g(lam)
    return Problem("logistic_l2", x_data, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, tau, mu, l,
                   prox_spec=(0.0, lam, np.inf))


def logistic_l1(x_data: jax.Array, y: jax.Array, lam: float,
                box: float = 10.0) -> Problem:
    """Sparse logistic regression (general convex case of Thm 2)."""
    f, grad_f, f_conj, tau = _logistic_f(y)
    g_el, g_conj_el, prox, mu, l = _l1_g(lam, box)
    return Problem("logistic_l1", x_data, f, grad_f, f_conj,
                   g_el, g_conj_el, prox, tau, mu, l,
                   prox_spec=(lam, 0.0, box))


PROBLEMS = {
    "ridge_primal": ridge_primal,
    "ridge_dual": ridge_dual,
    "lasso": lasso,
    "elastic_net": elastic_net,
    "logistic_l2": logistic_l2,
    "logistic_l1": logistic_l1,
}
