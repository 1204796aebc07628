"""Per-family transformer blocks: attention block with KV cache, dense/MoE
decoder layers, xLSTM pairs, Zamba2 hybrid groups, encoder/decoder layers.

All blocks share the signature
    apply(cfg, params, x, positions, cache, ctx) -> (y, new_cache, aux)
where ``cache=None`` selects the training path (no state materialized),
``positions`` are absolute token positions (B, S), and ``ctx`` carries the
optional mesh/axis info used by expert-parallel MoE.
"""
from __future__ import annotations

import dataclasses
from typing import Any

import jax
import jax.numpy as jnp
from jax import lax

from repro.configs.base import ModelConfig
from repro.models import ssm
from repro.models.attention import chunked_attention


def _attention(cfg, q, k, v, q_pos, kv_pos, *, mode):
    """Backend dispatch: jnp chunked scan (oracle) or the Pallas kernel
    (VMEM-resident tiles; interpret mode on CPU, Mosaic on TPU)."""
    if cfg.attn_backend == "pallas":
        from repro.kernels.ops import flash_attention_ops
        return flash_attention_ops(
            q, k, v, q_pos, kv_pos, mode=mode, window=cfg.window,
            block_q=min(128, max(8, q.shape[1])),
            block_kv=min(128, max(8, k.shape[1])))
    return chunked_attention(q, k, v, q_pos, kv_pos, mode=mode,
                             window=cfg.window, kv_chunk=cfg.scan_chunk,
                             compute_dtype=cfg.attn_compute_dtype)
from repro.models.common import (apply_rope, dense_init, head_rms_norm,
                                 rms_norm)
from repro.models.mlp import mlp_apply, mlp_init, moe_apply, moe_init


@dataclasses.dataclass(frozen=True)
class ModelCtx:
    """Runtime context: mesh/axes for expert parallelism, MoE mode, and the
    optional activation sharding constraint (a PartitionSpec for (B, S, D)
    hidden states applied at every scanned-layer boundary)."""

    mesh: Any = None
    model_axis: str | None = None
    moe_mode: str = "scatter"   # "scatter" | "dense"
    act_spec: Any = None        # PartitionSpec | None
    dispatch_groups: int = 0    # token-grouped MoE dispatch (see mlp.py)


DEFAULT_CTX = ModelCtx()


def _attn_mode(cfg: ModelConfig) -> str:
    return {"full": "causal", "sliding": "sliding",
            "chunked_local": "chunked_local"}[cfg.attention]


# ---------------------------------------------------------------------------
# GQA attention block with KV cache
# ---------------------------------------------------------------------------

def attn_init(key, cfg: ModelConfig, d_model: int | None = None) -> dict:
    d = d_model or cfg.d_model
    hd = cfg.resolved_head_dim
    dtype = jnp.dtype(cfg.param_dtype)
    ks = jax.random.split(key, 4)
    p = {
        "wq": dense_init(ks[0], (d, cfg.num_heads * hd), dtype),
        "wk": dense_init(ks[1], (d, cfg.num_kv_heads * hd), dtype),
        "wv": dense_init(ks[2], (d, cfg.num_kv_heads * hd), dtype),
        "wo": dense_init(ks[3], (cfg.num_heads * hd, d), dtype),
    }
    if cfg.qk_norm:
        p["q_scale"] = jnp.zeros((hd,), dtype=jnp.float32)
        p["k_scale"] = jnp.zeros((hd,), dtype=jnp.float32)
    return p


def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, dtype) -> dict:
    hd = cfg.resolved_head_dim
    return {
        "k": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype=dtype),
        "v": jnp.zeros((batch, max_len, cfg.num_kv_heads, hd), dtype=dtype),
        "pos": jnp.full((batch, max_len), -1, dtype=jnp.int32),
    }


def attn_apply(cfg: ModelConfig, p: dict, x: jax.Array, positions: jax.Array,
               cache: dict | None = None, *, mode: str | None = None):
    """Self attention. x: (B, S, d); positions: (B, S) absolute positions.

    With a cache, new K/V are written at slot ``position % cache_len`` (a ring
    buffer — for full caches sized >= seq_len this is the identity layout; for
    sliding-window caches sized `window` it implements SWA decode in O(window)
    memory).
    """
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    mode = mode or _attn_mode(cfg)
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k = (x @ p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (x @ p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    if cfg.qk_norm:
        q = head_rms_norm(q, p["q_scale"])
        k = head_rms_norm(k, p["k_scale"])
    q = apply_rope(q, positions, cfg.rope_theta)
    k = apply_rope(k, positions, cfg.rope_theta)

    if cache is None:
        out = _attention(cfg, q, k, v, positions, positions, mode=mode)
        new_cache = None
    else:
        cache_len = cache["k"].shape[1]
        # Attend over (old cache) ++ (fresh chunk): exact for one-token
        # decode, chunked prefill, and prompts longer than a ring buffer —
        # fresh keys are visible to the current chunk's queries even when
        # they won't all fit in the buffer afterwards. Prior positions can't
        # reappear in the fresh chunk, so there are no duplicate keys.
        k_att = jnp.concatenate([cache["k"].astype(q.dtype), k], axis=1)
        v_att = jnp.concatenate([cache["v"].astype(q.dtype), v], axis=1)
        pos_att = jnp.concatenate([cache["pos"], positions], axis=1)
        out = _attention(cfg, q, k_att, v_att, positions, pos_att, mode=mode)
        # ring-buffer write at slot = position % cache_len; a scatter handles
        # wrap-around, and prefills longer than the buffer keep only the last
        # cache_len tokens (older ones would be overwritten anyway).
        if s >= cache_len:
            k_w, v_w = k[:, -cache_len:], v[:, -cache_len:]
            pos_w = positions[:, -cache_len:]
        else:
            k_w, v_w, pos_w = k, v, positions
        slots = pos_w % cache_len                       # (B, S')
        bidx = jnp.arange(b)[:, None]
        ck = cache["k"].at[bidx, slots].set(k_w.astype(cache["k"].dtype))
        cv = cache["v"].at[bidx, slots].set(v_w.astype(cache["v"].dtype))
        cpos = cache["pos"].at[bidx, slots].set(pos_w)
        new_cache = {"k": ck, "v": cv, "pos": cpos}
    y = out.reshape(b, s, cfg.num_heads * hd) @ p["wo"]
    return y, new_cache


def cross_attn_init(key, cfg: ModelConfig) -> dict:
    return attn_init(key, cfg)


def cross_attn_apply(cfg: ModelConfig, p: dict, x: jax.Array,
                     enc_kv: tuple[jax.Array, jax.Array],
                     enc_pos: jax.Array):
    """Cross attention against precomputed encoder K/V."""
    b, s, d = x.shape
    hd = cfg.resolved_head_dim
    q = (x @ p["wq"]).reshape(b, s, cfg.num_heads, hd)
    k, v = enc_kv
    q_pos = jnp.zeros((b, s), dtype=jnp.int32)
    out = _attention(cfg, q, k, v, q_pos, enc_pos, mode="cross")
    return out.reshape(b, s, cfg.num_heads * hd) @ p["wo"]


def cross_kv(cfg: ModelConfig, p: dict, enc_out: jax.Array):
    b, s, _ = enc_out.shape
    hd = cfg.resolved_head_dim
    k = (enc_out @ p["wk"]).reshape(b, s, cfg.num_kv_heads, hd)
    v = (enc_out @ p["wv"]).reshape(b, s, cfg.num_kv_heads, hd)
    return k, v


# ---------------------------------------------------------------------------
# Decoder layers (dense & MoE)
# ---------------------------------------------------------------------------

def dense_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "attn": attn_init(k1, cfg),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def dense_layer_apply(cfg: ModelConfig, p: dict, x, positions, cache,
                      ctx: ModelCtx):
    h, new_cache = attn_apply(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                              cache)
    x = x + h
    x = x + mlp_apply(p["mlp"], rms_norm(x, p["ln2"]))
    return x, new_cache, jnp.zeros((), dtype=jnp.float32)


def moe_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "attn": attn_init(k1, cfg),
        "moe": moe_init(k2, cfg.d_model, cfg.d_ff, cfg.num_experts, dtype,
                        shared_expert=cfg.moe_shared_expert),
    }


def moe_layer_apply(cfg: ModelConfig, p: dict, x, positions, cache,
                    ctx: ModelCtx):
    h, new_cache = attn_apply(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                              cache)
    x = x + h
    y, aux = moe_apply(p["moe"], rms_norm(x, p["ln2"]),
                       experts_per_token=cfg.experts_per_token,
                       capacity_factor=cfg.moe_capacity_factor,
                       mode=ctx.moe_mode, mesh=ctx.mesh,
                       model_axis=ctx.model_axis,
                       dispatch_groups=ctx.dispatch_groups,
                       group_axes=(tuple(ctx.act_spec)[0]
                                   if ctx.act_spec is not None else None))
    return x + y, new_cache, aux


def moe_group_init(key, cfg: ModelConfig) -> dict:
    """Interleaved group (cfg.moe_every > 1): (moe_every - 1) dense layers
    followed by one MoE layer — llama4-style alternation."""
    ks = jax.random.split(key, cfg.moe_every)
    return {"dense": [dense_layer_init(k, cfg) for k in ks[:-1]],
            "moe": moe_layer_init(ks[-1], cfg)}


def moe_group_apply(cfg: ModelConfig, p: dict, x, positions, cache,
                    ctx: ModelCtx):
    n = cfg.moe_every - 1
    cache = cache or {"dense": [None] * n, "moe": None}
    new_dense = []
    for i in range(n):
        x, c, _ = dense_layer_apply(cfg, p["dense"][i], x, positions,
                                    cache["dense"][i], ctx)
        new_dense.append(c)
    x, cm, aux = moe_layer_apply(cfg, p["moe"], x, positions, cache["moe"],
                                 ctx)
    return x, {"dense": new_dense, "moe": cm}, aux


def moe_group_init_cache(cfg: ModelConfig, batch: int, max_len: int,
                         dtype) -> dict:
    return {"dense": [init_kv_cache(cfg, batch, max_len, dtype)
                      for _ in range(cfg.moe_every - 1)],
            "moe": init_kv_cache(cfg, batch, max_len, dtype)}


# ---------------------------------------------------------------------------
# xLSTM pair (mLSTM block + sLSTM block), each pre-norm residual
# ---------------------------------------------------------------------------

def xlstm_pair_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln_m": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln_s": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "mlstm": ssm.mlstm_init(k1, cfg.d_model, cfg.num_heads, dtype),
        "slstm": ssm.slstm_init(k2, cfg.d_model, cfg.num_heads, dtype),
    }


def xlstm_pair_apply(cfg: ModelConfig, p: dict, x, positions, cache,
                     ctx: ModelCtx):
    cache = cache or {"mlstm": None, "slstm": None}
    h, m_state = ssm.mlstm_apply(p["mlstm"], rms_norm(x, p["ln_m"]),
                                 num_heads=cfg.num_heads,
                                 chunk=cfg.scan_chunk, state=cache["mlstm"])
    x = x + h
    h, s_state = ssm.slstm_apply(p["slstm"], rms_norm(x, p["ln_s"]),
                                 num_heads=cfg.num_heads,
                                 state=cache["slstm"])
    x = x + h
    return x, {"mlstm": m_state, "slstm": s_state}, jnp.zeros((), jnp.float32)


def xlstm_init_cache(cfg: ModelConfig, p: dict, batch: int) -> dict:
    return {
        "mlstm": ssm.mlstm_init_state(p["mlstm"], batch, cfg.num_heads),
        "slstm": ssm.slstm_init_state(p["slstm"], batch, cfg.num_heads),
    }


# ---------------------------------------------------------------------------
# Zamba2 hybrid group: N mamba2 blocks + one SHARED attention block
# ---------------------------------------------------------------------------

def mamba_block_init(key, cfg: ModelConfig) -> dict:
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "mamba": ssm.mamba2_init(key, cfg.d_model, cfg.ssm_state, dtype,
                                 expand=cfg.ssm_expand,
                                 head_dim=cfg.ssm_head_dim),
    }


def hybrid_group_init(key, cfg: ModelConfig) -> dict:
    """One scanned group: ``blocks_per_attn`` mamba blocks + the layer norms
    feeding the SHARED attention+MLP block (whose params live outside the
    scan — Zamba2's parameter-sharing trick)."""
    ks = jax.random.split(key, cfg.blocks_per_attn)
    return {"mamba_blocks": [mamba_block_init(k, cfg) for k in ks],
            "ln_attn": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
            "ln_mlp": jnp.zeros((cfg.d_model,), dtype=jnp.float32)}


def hybrid_shared_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    return {"attn": attn_init(k1, cfg),
            "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff,
                            jnp.dtype(cfg.param_dtype))}


def hybrid_group_apply(cfg: ModelConfig, p: dict, shared: dict, x,
                       positions, cache, ctx: ModelCtx):
    n = cfg.blocks_per_attn
    cache = cache or {"mamba": [None] * n, "attn": None}
    new_mamba = []
    for i in range(n):
        blk = p["mamba_blocks"][i]
        h, st = ssm.mamba2_apply(blk["mamba"], rms_norm(x, blk["ln"]),
                                 ssm_state=cfg.ssm_state,
                                 chunk=cfg.scan_chunk,
                                 state=cache["mamba"][i])
        x = x + h
        new_mamba.append(st)
    h, attn_cache = attn_apply(cfg, shared["attn"], rms_norm(x, p["ln_attn"]),
                               positions, cache["attn"])
    x = x + h
    x = x + mlp_apply(shared["mlp"], rms_norm(x, p["ln_mlp"]))
    return x, {"mamba": new_mamba, "attn": attn_cache}, jnp.zeros((), jnp.float32)


def hybrid_init_cache(cfg: ModelConfig, p: dict, batch: int, max_len: int,
                      dtype) -> dict:
    return {
        "mamba": [ssm.mamba2_init_state(b["mamba"], batch, cfg.ssm_state)
                  for b in p["mamba_blocks"]],
        "attn": init_kv_cache(cfg, batch, max_len, dtype),
    }


# ---------------------------------------------------------------------------
# Encoder layer (bidirectional) and decoder layer with cross attention
# ---------------------------------------------------------------------------

def encoder_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2 = jax.random.split(key)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "attn": attn_init(k1, cfg),
        "mlp": mlp_init(k2, cfg.d_model, cfg.d_ff, dtype),
    }


def encoder_layer_apply(cfg: ModelConfig, p: dict, x, positions):
    h, _ = attn_apply(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                      None, mode="cross")  # bidirectional
    x = x + h
    x = x + mlp_apply(p["mlp"], rms_norm(x, p["ln2"]))
    return x


def decoder_xattn_layer_init(key, cfg: ModelConfig) -> dict:
    k1, k2, k3 = jax.random.split(key, 3)
    dtype = jnp.dtype(cfg.param_dtype)
    return {
        "ln1": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln_x": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "ln2": jnp.zeros((cfg.d_model,), dtype=jnp.float32),
        "attn": attn_init(k1, cfg),
        "xattn": cross_attn_init(k2, cfg),
        "mlp": mlp_init(k3, cfg.d_model, cfg.d_ff, dtype),
    }


def decoder_xattn_layer_apply(cfg: ModelConfig, p: dict, x, positions, cache,
                              enc_kv, enc_pos, ctx: ModelCtx):
    h, new_cache = attn_apply(cfg, p["attn"], rms_norm(x, p["ln1"]), positions,
                              cache)
    x = x + h
    x = x + cross_attn_apply(cfg, p["xattn"], rms_norm(x, p["ln_x"]), enc_kv,
                             enc_pos)
    x = x + mlp_apply(p["mlp"], rms_norm(x, p["ln2"]))
    return x, new_cache, jnp.zeros((), jnp.float32)
