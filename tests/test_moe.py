"""MoE block: routing invariants, gather-vs-capacity consistency, expert
parallelism via shard_map (subprocess with 8 host devices so the main test
process keeps jax on 1 device)."""
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import configs
from repro.common.arch_config import reduced
from repro.models import moe as moe_mod

import dataclasses


def _cfg(capacity=8.0):
    base = reduced(configs.get("granite-moe-1b-a400m"))
    return dataclasses.replace(base, capacity_factor=capacity)


def _params(cfg, key):
    from repro.models.layers import init_params
    return init_params(moe_mod.moe_specs(cfg), key)


def test_router_topk_and_aux():
    cfg = _cfg()
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (32, cfg.d_model))
    w, idx, aux = moe_mod._route(p, cfg, x)
    assert w.shape == (32, cfg.top_k) and idx.shape == (32, cfg.top_k)
    assert jnp.allclose(jnp.sum(w, -1), 1.0, atol=1e-5)  # renormalised
    assert bool(jnp.all(idx >= 0)) and bool(jnp.all(idx < cfg.n_experts))
    assert float(aux) >= 0.99  # aux >= 1 at optimum (E * sum f*p / k)


def test_gather_equals_capacity_when_dropfree():
    """The tiny-T decode path and the capacity path compute the same math."""
    cfg = _cfg(capacity=64.0)  # drop-free
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (8, cfg.d_model))
    w, idx, _ = moe_mod._route(p, cfg, x)
    out_cap = moe_mod._moe_capacity(p, cfg, x, w, idx, 0, cfg.n_experts)
    out_gat = moe_mod._moe_gather(p, cfg, x, w, idx)
    assert jnp.allclose(out_cap, out_gat, rtol=1e-4, atol=1e-5)


def test_capacity_partition_over_expert_slices():
    """Computing expert slices separately and summing == full pass
    (the shard_map psum decomposition, checked without a mesh)."""
    cfg = _cfg(capacity=64.0)
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (16, cfg.d_model))
    w, idx, _ = moe_mod._route(p, cfg, x)
    full = moe_mod._moe_capacity(p, cfg, x, w, idx, 0, cfg.n_experts)
    e_half = cfg.n_experts // 2

    def slice_params(lo, hi):
        return {"router": p["router"],
                "wi_gate": p["wi_gate"][lo:hi], "wi_up": p["wi_up"][lo:hi],
                "wo": p["wo"][lo:hi]}

    lo_half = moe_mod._moe_capacity(slice_params(0, e_half), cfg, x, w, idx,
                                    0, e_half)
    hi_half = moe_mod._moe_capacity(slice_params(e_half, cfg.n_experts), cfg,
                                    x, w, idx, e_half, e_half)
    assert jnp.allclose(lo_half + hi_half, full, rtol=1e-4, atol=1e-5)


def test_capacity_drops_overflow():
    cfg = _cfg(capacity=0.25)  # force drops
    p = _params(cfg, jax.random.PRNGKey(0))
    x = jax.random.normal(jax.random.PRNGKey(1), (64, cfg.d_model))
    w, idx, _ = moe_mod._route(p, cfg, x)
    out = moe_mod._moe_capacity(p, cfg, x, w, idx, 0, cfg.n_experts)
    # some tokens must have been dropped -> zero output rows exist
    norms = jnp.linalg.norm(out, axis=-1)
    assert float(jnp.min(norms)) < 1e-6
    assert bool(jnp.all(jnp.isfinite(out)))


SHARD_MAP_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses, jax, jax.numpy as jnp
from repro import configs
from repro.common.arch_config import reduced
from repro.models import moe as moe_mod
from repro.models.layers import init_params
from repro.launch.mesh import make_debug_mesh

cfg = dataclasses.replace(reduced(configs.get("granite-moe-1b-a400m")),
                          capacity_factor=64.0)
p = init_params(moe_mod.moe_specs(cfg), jax.random.PRNGKey(0))
x = jax.random.normal(jax.random.PRNGKey(1), (2, 16, cfg.d_model))
mesh = make_debug_mesh(2, 4)

local, aux_l = moe_mod.moe_block(p, cfg, x, mesh=None)
dist, aux_d = moe_mod.moe_block(p, cfg, x, mesh=mesh, dp_axes=("data",))
err = float(jnp.max(jnp.abs(local - dist)))
aux_err = abs(float(aux_l - aux_d))
assert err < 1e-4, f"shard_map mismatch: {err}"
# the load-balance aux is computed per data shard then averaged (standard
# Switch practice) -> small difference vs the global-batch aux
assert aux_err < 0.1, f"aux mismatch: {aux_err}"
print("SHARD_MAP_OK", err)
"""


def test_shard_map_expert_parallel_matches_local():
    res = subprocess.run(
        [sys.executable, "-c", SHARD_MAP_SNIPPET], capture_output=True,
        text=True, timeout=300,
        env={**__import__("os").environ, "PYTHONPATH": "src"},
        cwd=__import__("os").path.dirname(__import__("os").path.dirname(
            __file__)))
    assert "SHARD_MAP_OK" in res.stdout, res.stdout + res.stderr
