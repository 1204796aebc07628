"""Batched serving across architecture families: prefill a batch of prompts
and stream decode steps for a dense (SWA), an SSM and a hybrid model —
demonstrating the ring-buffer KV cache and O(1) recurrent decode state.

  PYTHONPATH=src python examples/serve_batched.py
"""
import time

import jax
import jax.numpy as jnp

from repro.configs.base import get_config, smoke_variant
from repro.models.model import build_model


def serve(arch: str, batch: int = 4, prompt_len: int = 32, gen: int = 16):
    cfg = smoke_variant(get_config(arch))
    api = build_model(cfg)
    params = api.init(jax.random.PRNGKey(0))
    prompt = jax.random.randint(jax.random.PRNGKey(1),
                                (batch, prompt_len), 0, cfg.vocab_size)
    cache = api.init_cache(params, batch, prompt_len + gen)
    decode = jax.jit(api.decode_step)

    t0 = time.time()
    logits, cache = api.prefill(params, {"tokens": prompt}, cache)
    tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    t_pre = time.time() - t0
    t0 = time.time()
    for i in range(gen - 1):
        logits, cache = decode(params, tok,
                               jnp.asarray(prompt_len + i, jnp.int32), cache)
        tok = jnp.argmax(logits[:, -1], axis=-1)[:, None]
    jax.block_until_ready(tok)
    t_dec = (time.time() - t0) / max(gen - 1, 1)
    state_bytes = sum(x.size * x.dtype.itemsize
                      for x in jax.tree.leaves(cache))
    print(f"{arch:18s} [{cfg.family:6s}] prefill {t_pre*1e3:7.1f} ms | "
          f"decode {t_dec*1e3:6.1f} ms/tok | decode-state "
          f"{state_bytes/1e6:6.2f} MB")


def main() -> None:
    for arch in ("h2o_danube3_4b", "xlstm_125m", "zamba2_7b"):
        serve(arch)


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
