"""Round-engine microbenchmark: per-round client training wall-clock,
sequential python-loop (`make_local_update` per client) vs the engine
path (`make_batched_local_update`, one jitted program that trains the
clients one after another).  Equal-size partitions, so neither path
builds padding; both are warmed up before timing so the numbers compare
steady-state rounds, not compiles.  Emits
``round_engine_K{K},us_per_round,speedup`` per client count.

    PYTHONPATH=src python -m benchmarks.round_engine_bench
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np

from benchmarks.common import emit, scale
from benchmarks.timing import time_rounds
from repro.core import mlp
from repro.core.client import (build_batched_batches, build_batches,
                               make_batched_local_update, make_local_update)
from repro.optim.optimizers import sgd

SAMPLES_PER_CLIENT = 256
BATCH = 32
EPOCHS = 8
LR = 0.05


def _problem(k: int, seed: int = 0):
    rng = np.random.default_rng(seed)
    n = k * SAMPLES_PER_CLIENT
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = rng.integers(0, 3, size=n)
    parts = [np.arange(i * SAMPLES_PER_CLIENT, (i + 1) * SAMPLES_PER_CLIENT)
             for i in range(k)]
    return x, y, parts


def run() -> None:
    rounds = scale(3, 10)
    net = mlp(2, 3, hidden=(32, 32))
    g = net.init(jax.random.PRNGKey(0))

    for k in (4, 8, 16):
        x, y, parts = _problem(k)

        upd = make_local_update(net, sgd(LR))
        per = [build_batches(x[idx], y[idx], BATCH, EPOCHS, seed=i)
               for i, idx in enumerate(parts)]
        per = [(jnp.asarray(xb), jnp.asarray(yb)) for xb, yb in per]

        def seq_round():
            outs = [upd(g, xb, yb, g) for xb, yb in per]
            jax.block_until_ready(outs[-1])

        bupd = make_batched_local_update(net, sgd(LR))
        xb, yb, mask = build_batched_batches(x, y, parts, BATCH, EPOCHS,
                                             seeds=list(range(k)))
        xb, yb, mask = jnp.asarray(xb), jnp.asarray(yb), jnp.asarray(mask)
        keys = jnp.zeros((k, 2), jnp.uint32)

        def bat_round():
            jax.block_until_ready(bupd(g, xb, yb, g, mask, keys))

        t_seq = time_rounds(seq_round, rounds)
        t_bat = time_rounds(bat_round, rounds)
        speedup = t_seq / t_bat
        emit(f"round_engine_K{k}", t_bat,
             f"speedup_x{speedup:.2f}",
             record={"n_clients": k, "seq_s": t_seq, "batched_s": t_bat,
                     "speedup": speedup, "steps_per_client":
                     EPOCHS * (SAMPLES_PER_CLIENT // BATCH)})


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
