"""Lasso with bounded support, written out plainly for the reference.

    min_x 0.5 ||A x - y||^2 + lam ||x||_1   subject to |x_i| <= box

f(v) = 0.5 ||v - y||^2 is 1-smooth (tau = 1), f*(w) = 0.5 ||w||^2 + <w, y>.
g_i(x) = lam |x| on [-box, box]; g_i*(u) = box * max(0, |u| - lam); its
support is bounded by L = box.
"""
import jax.numpy as jnp

TAU = 1.0


def support_bound(params):
    return float(params["box"])


def f(v, y, params):
    return 0.5 * jnp.sum((v - y) ** 2)


def grad_f(v, y, params):
    return v - y


def f_conj(w, y, params):
    return 0.5 * jnp.sum(w ** 2) + jnp.sum(w * y)


def g(x, params):
    outside = jnp.abs(x) > params["box"]
    return jnp.where(outside, jnp.inf, params["lam"] * jnp.abs(x))


def g_conj(u, params):
    return params["box"] * jnp.maximum(jnp.abs(u) - params["lam"], 0.0)


def prox(z, step, params):
    shrunk = jnp.sign(z) * jnp.maximum(jnp.abs(z) - step * params["lam"], 0.0)
    return jnp.clip(shrunk, -params["box"], params["box"])
