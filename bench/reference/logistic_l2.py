"""L2-regularised logistic regression, written out plainly for the reference.

    min_x sum_j log(1 + exp(-y_j (A x)_j)) + lam / 2 ||x||^2,   y_j in {-1, 1}

f is 1/4-smooth (tau = 4). With u_j = -w_j y_j in [0, 1], f*(w) is the
negative binary entropy sum_j u_j log u_j + (1 - u_j) log(1 - u_j).
g_i(x) = lam / 2 x^2, g_i*(u) = u^2 / (2 lam); its support is unbounded.
"""
import jax.numpy as jnp
from jax.scipy.special import xlogy

TAU = 4.0


def support_bound(params):
    return float("inf")


def f(v, y, params):
    return jnp.sum(jnp.logaddexp(0.0, -y * v))


def grad_f(v, y, params):
    return -y / (1.0 + jnp.exp(y * v))


def f_conj(w, y, params):
    u = jnp.clip(-w * y, 0.0, 1.0)
    return jnp.sum(xlogy(u, u) + xlogy(1.0 - u, 1.0 - u))


def g(x, params):
    return 0.5 * params["lam"] * x ** 2


def g_conj(u, params):
    return u ** 2 / (2.0 * params["lam"])


def prox(z, step, params):
    return z / (1.0 + step * params["lam"])
