"""Mixture-of-Experts block (Qwen3-MoE / Granite-MoE style).

Three execution paths, one math:

* ``_moe_capacity`` — sort-based capacity dispatch (no [T,E,C] one-hots, no
  fake dense-expert FLOPs).  Used for train / prefill.
* ``_moe_gather``  — per-token expert-weight gathering.  Used when
  ``T * top_k < n_experts`` (single-token decode): reads only the touched
  experts' weights, which is the true memory behaviour of MoE decode.
* ``moe_shard_map`` — expert-parallel wrapper: experts sharded over the
  "model" mesh axis, activations replicated over it, partial outputs
  psum-combined (communication pattern of TP-style expert parallelism).

Router: softmax gates, top-k, renormalised weights, Switch-style load-balance
auxiliary loss.
"""
from __future__ import annotations

import math
from functools import partial
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.common.arch_config import ArchConfig
from repro.models.layers import ParamSpec


def moe_specs(cfg: ArchConfig) -> dict:
    d, ff, e = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((d, e), ("embed", None)),
        "wi_gate": ParamSpec((e, d, ff), ("experts", "embed", "mlp")),
        "wi_up": ParamSpec((e, d, ff), ("experts", "embed", "mlp")),
        "wo": ParamSpec((e, ff, d), ("experts", "mlp", "embed")),
    }


def _route(p: dict, cfg: ArchConfig, x: jax.Array):
    """x: [T, d] -> (weights [T,k], idx [T,k], aux_loss scalar)."""
    logits = (x @ p["router"]).astype(jnp.float32)  # [T, E]
    gates = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(gates, cfg.top_k)
    w = w / jnp.sum(w, axis=-1, keepdims=True)
    # Switch-style load balance: E * sum_e f_e * P_e
    e = cfg.n_experts
    assign = jnp.zeros((x.shape[0], e), gates.dtype)
    assign = assign.at[jnp.arange(x.shape[0])[:, None], idx].set(1.0)
    f = jnp.mean(assign, axis=0)  # fraction routed (over top-k slots)
    pe = jnp.mean(gates, axis=0)
    aux = e * jnp.sum(f * pe) / cfg.top_k
    return w.astype(x.dtype), idx, aux


def _expert_ffn(p: dict, buf: jax.Array) -> jax.Array:
    """buf: [E_local, C, d] -> [E_local, C, d] (per-expert SwiGLU)."""
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", buf, p["wi_gate"]))
    u = jnp.einsum("ecd,edf->ecf", buf, p["wi_up"])
    return jnp.einsum("ecf,efd->ecd", g * u, p["wo"])


def _moe_capacity(p: dict, cfg: ArchConfig, x: jax.Array, w, idx,
                  e_start: int, e_local: int) -> jax.Array:
    """Sort-based capacity dispatch over the local expert slice."""
    t, d = x.shape
    k = cfg.top_k
    n = t * k
    cap = max(1, int(math.ceil(t * k / cfg.n_experts * cfg.capacity_factor)))

    fe = idx.reshape(n)
    fw = w.reshape(n)
    tok = jnp.arange(n) // k
    mine = (fe >= e_start) & (fe < e_start + e_local)
    le = jnp.where(mine, fe - e_start, e_local)  # e_local == drop bucket

    order = jnp.argsort(le)  # stable
    le_s = le[order]
    starts = jnp.searchsorted(le_s, jnp.arange(e_local))
    pos = jnp.arange(n) - starts[jnp.clip(le_s, 0, e_local - 1)]
    valid = (le_s < e_local) & (pos < cap)
    src = tok[order]

    e_idx = jnp.where(valid, le_s, e_local)  # out of range -> dropped
    p_idx = jnp.where(valid, pos, 0)
    buf = jnp.zeros((e_local, cap, d), x.dtype)
    buf = buf.at[e_idx, p_idx].set(x[src], mode="drop")

    y = _expert_ffn(p, buf)  # [e_local, cap, d]
    y_tok = y[jnp.clip(e_idx, 0, e_local - 1), p_idx]  # [n, d]
    y_tok = y_tok * (fw[order] * valid)[:, None]
    out = jnp.zeros((t, d), x.dtype).at[src].add(y_tok)
    return out


def _moe_gather(p: dict, cfg: ArchConfig, x: jax.Array, w, idx) -> jax.Array:
    """Tiny-T decode path: gather only the touched experts' weights."""
    wg = jnp.take(p["wi_gate"], idx, axis=0)  # [T, k, d, ff]
    wu = jnp.take(p["wi_up"], idx, axis=0)
    wo = jnp.take(p["wo"], idx, axis=0)  # [T, k, ff, d]
    g = jax.nn.silu(jnp.einsum("td,tkdf->tkf", x, wg))
    u = jnp.einsum("td,tkdf->tkf", x, wu)
    y = jnp.einsum("tkf,tkfd->tkd", g * u, wo)
    return jnp.einsum("tkd,tk->td", y, w)


def moe_block(p: dict, cfg: ArchConfig, x: jax.Array,
              mesh=None, dp_axes: Tuple[str, ...] = ()) -> Tuple[jax.Array, jax.Array]:
    """x: [B, S, d] -> (out [B, S, d], aux loss).

    If ``mesh`` is given and the token count divides the data axes, run
    expert-parallel via shard_map; otherwise run the local path (correct on
    one device, and what serve_step uses).
    """
    b, s, d = x.shape
    x2 = x.reshape(b * s, d)
    t = b * s

    if mesh is not None and "model" in mesh.axis_names:
        dp = tuple(a for a in dp_axes if a in mesh.axis_names)
        dp_size = math.prod(mesh.shape[a] for a in dp) if dp else 1
        m_size = mesh.shape["model"]
        if (t % max(dp_size, 1) == 0 and cfg.n_experts % m_size == 0
                and t >= dp_size and t * cfg.top_k >= cfg.n_experts):
            out, aux = _moe_shard_map(p, cfg, x2, mesh, dp)
            return out.reshape(b, s, d), aux

    w, idx, aux = _route(p, cfg, x2)
    if t * cfg.top_k < cfg.n_experts:
        out = _moe_gather(p, cfg, x2, w, idx)
    else:
        out = _moe_capacity(p, cfg, x2, w, idx, 0, cfg.n_experts)
    return out.reshape(b, s, d), aux


def _moe_shard_map(p: dict, cfg: ArchConfig, x2: jax.Array, mesh, dp):
    m_size = mesh.shape["model"]
    e_local = cfg.n_experts // m_size

    def local_fn(router, wg, wu, wo, xl):
        # xl: [T_local, d]; expert weights: local slice [e_local, ...]
        pl = {"router": router, "wi_gate": wg, "wi_up": wu, "wo": wo}
        w, idx, aux = _route(pl, cfg, xl)
        midx = jax.lax.axis_index("model")
        out = _moe_capacity(pl, cfg, xl, w, idx, midx * e_local, e_local)
        out = jax.lax.psum(out, "model")
        aux = jax.lax.pmean(aux, dp) if dp else aux
        return out, aux

    dp_spec = dp if len(dp) > 1 else (dp[0] if dp else None)
    in_specs = (
        P(None, None),                 # router replicated
        P("model", None, None),        # experts sharded
        P("model", None, None),
        P("model", None, None),
        P(dp_spec, None),              # tokens over data axes
    )
    out_specs = (P(dp_spec, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, check_vma=False)
    return fn(p["router"], p["wi_gate"], p["wi_up"], p["wo"], x2)
