"""Pallas TPU kernel for the Mamba2 chunked SSD scan.

TPU adaptation of the CUDA selective-scan: instead of a warp-level
associative scan, the sequence is chunked (Q tokens) and each chunk becomes
dense matmul work for the MXU (intra-chunk kernel matrix + state outer
products); the only sequential part is a [H, N, P] running state carried in
VMEM scratch across the chunk grid dimension.

Grid: (B, H/bh, n_chunks) — chunks innermost ("arbitrary" semantics, the
state scratch persists across them); batch and head tiles parallel.

Per-invocation VMEM working set (fp32):
    x, y: 2*Q*bh*P   kernel matrix: Q*Q*bh   state: bh*N*P   B,C: 2*Q*N
e.g. Q=128, bh=8, P=64, N=128: ~1.3 MB — comfortably inside 16 MB VMEM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _ssd_kernel(x_ref, dt_ref, a_ref, b_ref, c_ref, y_ref, state, *,
                s_total: int, q: int):
    ci = pl.program_id(2)

    @pl.when(ci == 0)
    def _init():
        state[...] = jnp.zeros_like(state)

    x = x_ref[0, 0].astype(jnp.float32)      # [Q, bh, P]
    dt = dt_ref[0, 0].astype(jnp.float32)    # [Q, bh]
    a = -jnp.exp(a_ref[...].astype(jnp.float32))  # [bh]
    bm = b_ref[0, 0].astype(jnp.float32)     # [Q, N]
    cm = c_ref[0, 0].astype(jnp.float32)     # [Q, N]

    # zero out the padded tail of the final chunk
    pos = ci * q + jax.lax.broadcasted_iota(jnp.int32, dt.shape, 0)
    valid = pos < s_total
    dt = jnp.where(valid, dt, 0.0)  # pad steps: decay=1, no input

    da = dt * a[None, :]                     # [Q, bh]
    cum = jnp.cumsum(da, axis=0)             # [Q, bh]

    # inter-chunk: y_q = exp(cum_q) * C_q . state_in
    y_inter = jnp.einsum("qn,hnp->qhp", cm, state[...]) * \
        jnp.exp(cum)[:, :, None]

    # intra-chunk: decay-masked kernel matrix
    seg = cum[:, None, :] - cum[None, :, :]  # [Q, Q, bh]
    tril = jax.lax.broadcasted_iota(jnp.int32, (q, q), 0) >= \
        jax.lax.broadcasted_iota(jnp.int32, (q, q), 1)
    decay = jnp.where(tril[:, :, None], jnp.exp(seg), 0.0)
    cb = jnp.einsum("qn,jn->qj", cm, bm)     # [Q, Q]
    kern = cb[:, :, None] * decay * dt[None, :, :]
    y_intra = jnp.einsum("qjh,jhp->qhp", kern, x)

    y_ref[0, 0] = (y_intra + y_inter).astype(y_ref.dtype)

    # state update: S <- exp(cum_end) S + sum_j exp(cum_end - cum_j) dt_j B_j x_j
    decay_end = jnp.exp(cum[-1][None, :] - cum) * dt  # [Q, bh]
    new_state = state[...] * jnp.exp(cum[-1])[:, None, None] + jnp.einsum(
        "qh,qn,qhp->hnp", decay_end, bm, x)
    state[...] = new_state


def ssd_scan_pallas(x, dt, a_log, bmat, cmat, chunk: int = 128,
                    block_h: int = 8, interpret: bool = True):
    """x:[B,S,H,P] dt:[B,S,H] a_log:[H] b/c:[B,S,N] -> y [B,S,H,P]."""
    b, s, h, p = x.shape
    n = bmat.shape[-1]
    q = min(chunk, max(8, s))
    pad_s = (-s) % q
    bh = min(block_h, h)
    pad_h = (-h) % bh
    if pad_s or pad_h:
        x = jnp.pad(x, ((0, 0), (0, pad_s), (0, pad_h), (0, 0)))
        dt = jnp.pad(dt, ((0, 0), (0, pad_s), (0, pad_h)))
        a_log = jnp.pad(a_log, ((0, pad_h),))
        bmat = jnp.pad(bmat, ((0, 0), (0, pad_s), (0, 0)))
        cmat = jnp.pad(cmat, ((0, 0), (0, pad_s), (0, 0)))
    sp, hp = s + pad_s, h + pad_h
    nc = sp // q

    xc = x.reshape(b, nc, q, hp, p)
    dtc = dt.reshape(b, nc, q, hp)
    bc = bmat.reshape(b, nc, q, n)
    cc = cmat.reshape(b, nc, q, n)

    kern = functools.partial(_ssd_kernel, s_total=s, q=q)
    y = pl.pallas_call(
        kern,
        grid=(b, hp // bh, nc),
        in_specs=[
            pl.BlockSpec((1, 1, q, bh, p), lambda bi, hi, ci: (bi, ci, 0, hi, 0)),
            pl.BlockSpec((1, 1, q, bh), lambda bi, hi, ci: (bi, ci, 0, hi)),
            pl.BlockSpec((bh,), lambda bi, hi, ci: (hi,)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
            pl.BlockSpec((1, 1, q, n), lambda bi, hi, ci: (bi, ci, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, q, bh, p),
                               lambda bi, hi, ci: (bi, ci, 0, hi, 0)),
        out_shape=jax.ShapeDtypeStruct((b, nc, q, hp, p), x.dtype),
        scratch_shapes=[pltpu.VMEM((bh, n, p), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary")),
        interpret=interpret,
    )(xc, dtc, a_log, bc, cc)
    return y.reshape(b, sp, hp, p)[:, :s, :h]
