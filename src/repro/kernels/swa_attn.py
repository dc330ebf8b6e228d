"""Pallas TPU kernel: flash-style causal attention with sliding window.

Used by gemma3's local layers (5 of every 6).  The win over plain flash
attention is structural: for a window ``w`` and query block ``bq``, each
query block only visits ``ceil(w/bk)+1`` KV blocks instead of all preceding
ones — O(S*w) instead of O(S^2) compute *and* HBM reads.

Grid: (B*H, S/bq, n_kv_blocks) with the KV dimension innermost; the KV
block index is *relative*: absolute kv block = q_block - n_rel + 1 + j,
clamped to 0 by the index_map and exactly masked inside the kernel (an
out-of-range relative block contributes nothing, so clamp-duplicates are
killed by the mask on intended-vs-actual block id).

Online softmax accumulators (m, l, o) persist in VMEM scratch across the KV
iterations of one query block.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _swa_kernel(q_ref, k_ref, v_ref, o_ref, m_acc, l_acc, o_acc, *,
                bq: int, bk: int, n_rel: int, window: int | None,
                s_total: int, scale: float):
    qi = pl.program_id(1)
    j = pl.program_id(2)

    @pl.when(j == 0)
    def _init():
        m_acc[...] = jnp.full_like(m_acc, NEG)
        l_acc[...] = jnp.zeros_like(l_acc)
        o_acc[...] = jnp.zeros_like(o_acc)

    intended = qi + j - (n_rel - 1)  # relative -> absolute kv block
    q = q_ref[0].astype(jnp.float32) * scale   # [bq, d]
    k = k_ref[0].astype(jnp.float32)           # [bk, d]
    v = v_ref[0].astype(jnp.float32)           # [bk, d]

    s = q @ k.T  # [bq, bk]
    q_pos = qi * bq + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 0)
    k_pos = intended * bk + jax.lax.broadcasted_iota(jnp.int32, (bq, bk), 1)
    mask = (k_pos <= q_pos) & (k_pos >= 0) & (intended >= 0)
    mask &= (q_pos < s_total) & (k_pos < s_total)
    if window is not None:
        mask &= (q_pos - k_pos) < window
    s = jnp.where(mask, s, NEG)

    m_new = jnp.maximum(m_acc[...], jnp.max(s, -1, keepdims=True))
    p = jnp.exp(s - m_new)
    p = jnp.where(mask, p, 0.0)
    alpha = jnp.exp(m_acc[...] - m_new)
    l_acc[...] = l_acc[...] * alpha + jnp.sum(p, -1, keepdims=True)
    o_acc[...] = o_acc[...] * alpha + p @ v
    m_acc[...] = m_new

    @pl.when(j == pl.num_programs(2) - 1)
    def _finish():
        o_ref[0] = (o_acc[...] / jnp.maximum(l_acc[...], 1e-30)).astype(
            o_ref.dtype)


def swa_attn_pallas(q, k, v, window: int | None, *, block: int = 128,
                    interpret: bool = True):
    """q/k/v: [B, H, S, D] -> [B, H, S, D]; causal (+ window if not None).

    Q and KV share one block size so the relative-block arithmetic in the
    kernel is exact."""
    b, h, s, d = q.shape
    bq = bk = min(block, max(8, s))
    pad_s = (-s) % bq
    if pad_s:
        pad = ((0, 0), (0, 0), (0, pad_s), (0, 0))
        q, k, v = jnp.pad(q, pad), jnp.pad(k, pad), jnp.pad(v, pad)
    sp = s + pad_s
    qf = q.reshape(b * h, sp, d)
    kf = k.reshape(b * h, sp, d)
    vf = v.reshape(b * h, sp, d)

    if window is None:
        n_rel = sp // bk  # all preceding blocks (full causal)
    else:
        n_rel = min(sp // bk, math.ceil(window / bk) + 1)

    kern = functools.partial(
        _swa_kernel, bq=bq, bk=bk, n_rel=n_rel, window=window, s_total=s,
        scale=1.0 / math.sqrt(d))

    def kv_index(bi, qi, j):
        return (bi, _clamp(qi + j - (n_rel - 1), sp // bk), 0)

    out = pl.pallas_call(
        kern,
        grid=(b * h, sp // bq, n_rel),
        in_specs=[
            pl.BlockSpec((1, bq, d), lambda bi, qi, j: (bi, qi, 0)),
            pl.BlockSpec((1, bk, d), kv_index),
            pl.BlockSpec((1, bk, d), kv_index),
        ],
        out_specs=pl.BlockSpec((1, bq, d), lambda bi, qi, j: (bi, qi, 0)),
        out_shape=jax.ShapeDtypeStruct((b * h, sp, d), q.dtype),
        scratch_shapes=[
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, 1), jnp.float32),
            pltpu.VMEM((bq, d), jnp.float32),
        ],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(qf, kf, vf)
    return out.reshape(b, h, sp, d)[:, :, :s]


def _clamp(x, n_blocks):
    return jnp.clip(x, 0, n_blocks - 1)
