"""Pallas TPU flash attention with GQA + positional masking.

The kernel is the TPU adaptation of the zoo's attention hot path: the
(Sq, Skv) score matrix never leaves VMEM — a (block_q, head_dim) query tile
and running (m, l, acc) statistics live in VMEM scratch while the kernel
walks KV tiles along the last (sequential) grid axis. All four variants of
``repro.models.attention`` (causal / sliding / chunked_local / cross) are
expressed through the same explicit-position masking, so ring-buffer decode
caches work unchanged.

Grid: (batch, kv_head, q_group, num_q_blocks, num_kv_blocks) — the KV axis is
last, so on TPU the scratch accumulators carry across KV tiles of one query
tile (the sequential-grid idiom). Block shapes are MXU-aligned: block_q x
head_dim and block_kv x head_dim tiles with head_dim a multiple of 128 in the
production configs.

``ops.flash_attention`` is the jit'd wrapper (drop-in for
``chunked_attention``); ``ref.py`` is the pure-jnp oracle.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30


def _mask_block(mode: str, qp, kp, window: int):
    """(Bq, Bk) boolean mask from position tiles (same math as _mode_mask)."""
    q = qp[:, None]
    k = kp[None, :]
    valid = k >= 0
    if mode == "causal":
        return valid & (k <= q)
    if mode == "sliding":
        return valid & (k <= q) & (k > q - window)
    if mode == "chunked_local":
        return valid & (k <= q) & ((k // window) == (q // window))
    if mode == "cross":
        return valid
    raise ValueError(mode)


def _flash_kernel(q_ref, k_ref, v_ref, qp_ref, kp_ref, o_ref,
                  m_ref, l_ref, acc_ref, *, mode: str, window: int,
                  scale: float, num_kv_blocks: int):
    kv_i = pl.program_id(4)

    @pl.when(kv_i == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0, 0].astype(jnp.float32) * scale       # (Bq, hd)
    k = k_ref[0, 0].astype(jnp.float32)                  # (Bk, hd)
    v = v_ref[0, 0].astype(jnp.float32)                  # (Bk, hd)
    qp = qp_ref[0]                                       # (Bq,)
    kp = kp_ref[0]                                       # (Bk,)

    s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())))  # (Bq, Bk)
    mask = _mask_block(mode, qp, kp, window)
    s = jnp.where(mask, s, NEG_INF)

    m_prev = m_ref[...]
    m_new = jnp.maximum(m_prev, jnp.max(s, axis=1))
    alpha = jnp.exp(m_prev - m_new)
    p = jnp.exp(s - m_new[:, None])
    # fully-masked rows: keep p at 0 (s - m_new would be NEG_INF - NEG_INF)
    p = jnp.where(mask, p, 0.0)
    l_ref[...] = l_ref[...] * alpha + jnp.sum(p, axis=1)
    acc_ref[...] = (acc_ref[...] * alpha[:, None]
                    + jax.lax.dot_general(p, v, (((1,), (0,)), ((), ()))))
    m_ref[...] = m_new

    @pl.when(kv_i == num_kv_blocks - 1)
    def _finalize():
        o_ref[0, 0, 0] = (acc_ref[...]
                          / jnp.maximum(l_ref[...], 1e-30)[:, None]
                          ).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("mode", "window", "block_q",
                                             "block_kv", "interpret"))
def flash_attention(q: jax.Array, k: jax.Array, v: jax.Array,
                    q_pos: jax.Array, kv_pos: jax.Array, *, mode: str,
                    window: int = 0, block_q: int = 128, block_kv: int = 128,
                    interpret: bool = True) -> jax.Array:
    """Flash GQA attention via pl.pallas_call.

    Args mirror ``repro.models.attention.chunked_attention``:
      q: (B, Sq, H, hd); k, v: (B, Skv, KV, hd), H = G * KV.
      q_pos: (B, Sq) int32; kv_pos: (B, Skv) int32, -1 = empty slot.

    ``interpret=True`` executes the kernel body with the Pallas
    interpreter; on a TPU pass ``interpret=False`` (``ops.flash_attention_ops``
    chooses by backend).
    """
    b, sq, h, hd = q.shape
    skv, kvh = k.shape[1], k.shape[2]
    g = h // kvh
    scale = hd ** -0.5

    block_q = min(block_q, sq)
    block_kv = min(block_kv, skv)
    # pad sequences to block multiples; padded kv slots get position -1
    pad_q = (-sq) % block_q
    pad_kv = (-skv) % block_kv
    if pad_q:
        q = jnp.pad(q, ((0, 0), (0, pad_q), (0, 0), (0, 0)))
        q_pos = jnp.pad(q_pos, ((0, 0), (0, pad_q)))
    if pad_kv:
        k = jnp.pad(k, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad_kv), (0, 0), (0, 0)))
        kv_pos = jnp.pad(kv_pos, ((0, 0), (0, pad_kv)), constant_values=-1)
    sq_p, skv_p = sq + pad_q, skv + pad_kv
    nq, nk = sq_p // block_q, skv_p // block_kv

    # (B, KV, G, Sq, hd) so the head group axes are grid axes
    qt = q.reshape(b, sq_p, kvh, g, hd).transpose(0, 2, 3, 1, 4)
    kt = k.transpose(0, 2, 1, 3)                         # (B, KV, Skv, hd)
    vt = v.transpose(0, 2, 1, 3)

    grid = (b, kvh, g, nq, nk)
    out = pl.pallas_call(
        functools.partial(_flash_kernel, mode=mode, window=window,
                          scale=scale, num_kv_blocks=nk),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, 1, 1, block_q, hd),
                         lambda bi, ki, gi, qi, kvi: (bi, ki, gi, qi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bi, ki, gi, qi, kvi: (bi, ki, kvi, 0)),
            pl.BlockSpec((1, 1, block_kv, hd),
                         lambda bi, ki, gi, qi, kvi: (bi, ki, kvi, 0)),
            pl.BlockSpec((1, block_q),
                         lambda bi, ki, gi, qi, kvi: (bi, qi)),
            pl.BlockSpec((1, block_kv),
                         lambda bi, ki, gi, qi, kvi: (bi, kvi)),
        ],
        out_specs=pl.BlockSpec((1, 1, 1, block_q, hd),
                               lambda bi, ki, gi, qi, kvi: (bi, ki, gi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, kvh, g, sq_p, hd), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((block_q,), jnp.float32),      # m
            pltpu.VMEM((block_q,), jnp.float32),      # l
            pltpu.VMEM((block_q, hd), jnp.float32),   # acc
        ],
        interpret=interpret,
    )(qt, kt, vt, q_pos, kv_pos)

    out = out.transpose(0, 3, 1, 2, 4).reshape(b, sq_p, h, hd)
    return out[:, :sq]
