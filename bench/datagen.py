"""The configurations' data, drawn on the device from the run's seed.

The distributions are those of the paper's synthetic sets (Fig. 1): entries
of A are standard normal over sqrt(d), so columns are about unit norm; the
ground truth is sparse with normal non-zeros. ``regression`` adds Gaussian
noise to y = A w; ``classification`` draws labels in {-1, +1} from a
logistic model with logits 5 * A w. The numbers differ from a host draw of
the same distributions: A is drawn in row chunks by the device's counter
RNG, inside one jitted call, so that the generator's transient memory is one
chunk and the only full-size array it leaves is A itself.
"""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
from jax import lax

HIGHEST = lax.Precision.HIGHEST
MAX_CHUNK_BYTES = 256 * 2 ** 20


def key_from_seed(seed: int) -> jax.Array:
    """A PRNG key from any whole number, also one wider than 32 bits."""
    seed %= 2 ** 64
    key = jax.random.PRNGKey(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, seed >> 32)


def row_chunk(d: int, n: int, itemsize: int = 4) -> int:
    """The largest divisor of d whose rows fit MAX_CHUNK_BYTES."""
    most = max(1, MAX_CHUNK_BYTES // (n * itemsize))
    for parts in range(-(-d // most), d + 1):
        if d % parts == 0:
            return d // parts
    return 1


@partial(jax.jit, static_argnames=("d", "n", "nnz", "noise", "logistic",
                                   "chunk"))
def _draw(key, *, d, n, nnz, noise, logistic, chunk):
    k_idx, k_val, k_a, k_y = jax.random.split(key, 4)
    support = jax.random.permutation(k_idx, n)[:nnz]
    w = jnp.zeros((n,), jnp.float32).at[support].set(
        jax.random.normal(k_val, (nnz,), jnp.float32))
    scale = 1.0 / math.sqrt(d)

    def body(i, carry):
        a, y = carry
        blk = jax.random.normal(jax.random.fold_in(k_a, i), (chunk, n),
                                jnp.float32) * scale
        aw = jnp.matmul(blk, w, precision=HIGHEST)
        k_i = jax.random.fold_in(k_y, i)
        if logistic:
            p = jax.nn.sigmoid(5.0 * aw)
            yb = jnp.where(jax.random.uniform(k_i, (chunk,)) < p, 1.0, -1.0)
        else:
            yb = aw + noise * jax.random.normal(k_i, (chunk,), jnp.float32)
        a = lax.dynamic_update_slice(a, blk, (i * chunk, 0))
        y = lax.dynamic_update_slice(y, yb.astype(jnp.float32), (i * chunk,))
        return a, y

    a0 = jnp.zeros((d, n), jnp.float32)
    y0 = jnp.zeros((d,), jnp.float32)
    return lax.fori_loop(0, d // chunk, body, (a0, y0))


def draw(data: dict, seed: int):
    """(A, y) of the configuration's ``data`` block, on the default device.

    ``data``: {"kind": "regression" | "classification", "samples": d,
    "features": n, "solution_density": share of non-zeros in the truth,
    "noise": std of the regression noise}.
    """
    d, n = int(data["samples"]), int(data["features"])
    kind = data["kind"]
    if kind not in ("regression", "classification"):
        raise ValueError(f"unknown data kind {kind!r}")
    return _draw(key_from_seed(seed), d=d, n=n,
                 nnz=max(1, int(data["solution_density"] * n)),
                 noise=float(data.get("noise", 0.0)),
                 logistic=kind == "classification", chunk=row_chunk(d, n))
