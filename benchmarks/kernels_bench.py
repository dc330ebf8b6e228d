"""Microbenchmarks of the Pallas kernels vs their jnp references.

NOTE: on this CPU container the kernels run in INTERPRET mode (a Python
loop over grid cells) — wall time here is a correctness-path benchmark,
not TPU performance; the TPU roofline story lives in EXPERIMENTS.md.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.kernels import ref
from repro.kernels.ensemble_kl import ensemble_kl
from repro.kernels.ssd_scan import ssd_scan_pallas
from repro.kernels.swa_attn import swa_attn_pallas


def _time(fn, *args, reps=3):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.time()
    for _ in range(reps):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / reps


def run(seed: int = 0) -> dict:
    key = jax.random.PRNGKey(seed)
    results = {}

    # ensemble_kl: FedDF loss at K=8 teachers, 16k vocab
    k1, k2 = jax.random.split(key)
    s = jax.random.normal(k1, (16, 16384))
    t = jax.random.normal(k2, (8, 16, 16384))
    jr = jax.jit(lambda a, b: ref.ensemble_kl(a, b, 1.0))
    tk = _time(lambda a, b: ensemble_kl(a, b, 1.0, 8, True), s, t)
    tr = _time(jr, s, t)
    err = abs(float(ensemble_kl(s, t, 1.0) - ref.ensemble_kl(s, t, 1.0)))
    emit("kernel_ensemble_kl_interp", tk, f"ref_jit={tr*1e6:.0f}us,err={err:.1e}",
         {"kernel_s": tk, "ref_s": tr, "err": err})
    results["ensemble_kl"] = {"kernel_s": tk, "ref_s": tr, "err": err}

    # ssd_scan
    ks = jax.random.split(key, 5)
    b, ss, h, p, n = 1, 256, 4, 32, 16
    x = jax.random.normal(ks[0], (b, ss, h, p))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, ss, h))) * 0.1
    a_log = jax.random.normal(ks[2], (h,)) * 0.5
    bm = jax.random.normal(ks[3], (b, ss, n)) * 0.5
    cm = jax.random.normal(ks[4], (b, ss, n)) * 0.5
    jrs = jax.jit(lambda *a: ref.ssd_scan(*a, 64))
    tks = _time(lambda *a: ssd_scan_pallas(*a, chunk=64, block_h=4),
                x, dt, a_log, bm, cm)
    trs = _time(jrs, x, dt, a_log, bm, cm)
    emit("kernel_ssd_scan_interp", tks, f"ref_jit={trs*1e6:.0f}us",
         {"kernel_s": tks, "ref_s": trs})
    results["ssd_scan"] = {"kernel_s": tks, "ref_s": trs}

    # swa_attn
    q = jax.random.normal(ks[0], (1, 4, 512, 64))
    kk = jax.random.normal(ks[1], (1, 4, 512, 64))
    v = jax.random.normal(ks[2], (1, 4, 512, 64))
    jra = jax.jit(lambda *a: ref.swa_attn(*a, 128))
    tka = _time(lambda *a: swa_attn_pallas(*a, 128, block=128), q, kk, v)
    tra = _time(jra, q, kk, v)
    emit("kernel_swa_attn_interp", tka, f"ref_jit={tra*1e6:.0f}us",
         {"kernel_s": tka, "ref_s": tra})
    results["swa_attn"] = {"kernel_s": tka, "ref_s": tra}
    return results


if __name__ == "__main__":
    from repro.common.compile_cache import use_compile_cache
    use_compile_cache()
    run()
