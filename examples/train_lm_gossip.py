"""End-to-end driver: train the ~125M-parameter xLSTM on synthetic tokens —
once with canonical all-reduce data parallelism, once with CoLA-style gossip
data parallelism (4 node replicas on a ring, Metropolis parameter mixing, no
global collective), and compare loss + consensus trajectories.

Full-size run (slow on CPU):
  PYTHONPATH=src python examples/train_lm_gossip.py --steps 300
Quick demo (reduced config):
  PYTHONPATH=src python examples/train_lm_gossip.py --smoke --steps 30
"""
import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import get_config, smoke_variant
from repro.optim import gossip as gsp
from repro.train.data import TokenBatches
from repro.train.steps import TrainHParams, init_train_state, make_train_step


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--steps", type=int, default=300)
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--nodes", type=int, default=4)
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--seq", type=int, default=128)
    args = ap.parse_args()

    cfg = get_config("xlstm_125m")
    if args.smoke:
        cfg = smoke_variant(cfg)
    hp = TrainHParams(lr=1e-3)
    pipe = TokenBatches(cfg.vocab_size, args.batch, args.seq, seed=0)
    print(f"model: {cfg.name} ({'smoke' if args.smoke else 'full ~125M'})")

    # --- baseline: single-replica (== all-reduce DP semantics) -------------
    state = init_train_state(cfg, jax.random.PRNGKey(0), hp)
    step = jax.jit(make_train_step(cfg, hp))
    t0 = time.time()
    for i in range(args.steps):
        state, m = step(state, jax.tree.map(jnp.asarray, pipe(i)))
        if i % max(args.steps // 10, 1) == 0:
            print(f"[all-reduce] step {i:4d} loss {float(m['loss']):.4f} "
                  f"({(time.time()-t0)/(i+1):.2f}s/step)", flush=True)
    base_loss = float(m["loss"])

    # --- CoLA gossip-DP: K replicas, ring mixing, node-local data ----------
    k = args.nodes
    gcfg = gsp.GossipConfig(num_nodes=k, topology="ring")
    states = gsp.replicate_state(init_train_state(cfg, jax.random.PRNGKey(0),
                                                  hp), k)
    gstep = gsp.make_gossip_step(make_train_step(cfg, hp), gcfg)
    w = jnp.asarray(gcfg.weights(), jnp.float32)
    act = jnp.ones((k,), jnp.float32)
    t0 = time.time()
    for i in range(args.steps):
        batches = jax.tree.map(
            jnp.asarray, jax.tree.map(lambda *xs: np.stack(xs),
                                      *[pipe(i, shard=j) for j in range(k)]))
        states, m = gstep(states, batches, w, act)
        if i % max(args.steps // 10, 1) == 0:
            print(f"[gossip-DP ] round {i:4d} mean-loss "
                  f"{float(jnp.mean(m['loss'])):.4f} consensus "
                  f"{float(gsp.consensus_distance(states.params)):.3e} "
                  f"({(time.time()-t0)/(i+1):.2f}s/round)", flush=True)
    print(f"\nfinal: all-reduce loss {base_loss:.4f} | gossip mean loss "
          f"{float(jnp.mean(m['loss'])):.4f} (each gossip node saw {k}x the "
          f"data at 1/{k} the per-round collective cost)")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
