"""Jit'd public wrappers around the Pallas kernels.

On a TPU the kernels always compile; on any other backend they run in
interpret mode (which exists for testing, not speed).
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp

from repro.kernels.ensemble_kl import ensemble_kl as _ensemble_kl
from repro.kernels.ensemble_kl import ensemble_kl_bank as _ensemble_kl_bank
from repro.kernels.ensemble_kl import ensemble_kl_pre as _ensemble_kl_pre
from repro.kernels.ssd_scan import ssd_scan_pallas as _ssd
from repro.kernels.swa_attn import swa_attn_pallas as _swa


def _interpret() -> bool:
    """True only off TPU: on a TPU the kernels always compile, so
    ``use_fused_kernel='auto'`` lands on the compiled kernel."""
    return jax.default_backend() != "tpu"


def use_pallas(flag) -> bool:
    """Resolve a ``use_fused_kernel`` setting.  ``'auto'`` selects the
    Pallas kernels on TPU and the plain-jnp reference path elsewhere
    (interpret mode exists for testing, not speed); booleans are taken
    literally; any other string is a loud error (``bool("off")`` would
    silently enable the kernel)."""
    from repro.common.options import FUSED_KERNEL_MODES
    if flag == "auto":
        return jax.default_backend() == "tpu"
    if not isinstance(flag, bool):
        raise ValueError(f"use_fused_kernel must be one of "
                         f"{FUSED_KERNEL_MODES}, got {flag!r}")
    return flag


def ensemble_kl_loss(student_logits: jax.Array, teacher_logits: jax.Array,
                     temperature: float = 1.0) -> jax.Array:
    """FedDF AVGLOGITS loss. student: [..., V]; teachers: [K, ..., V].

    Leading dims are flattened into rows; differentiable w.r.t. the student
    logits via the fused backward kernel.
    """
    v = student_logits.shape[-1]
    k = teacher_logits.shape[0]
    s2 = student_logits.reshape(-1, v)
    t2 = teacher_logits.reshape(k, -1, v)
    return _ensemble_kl(s2, t2, temperature, 8, _interpret())


def ensemble_kl_loss_pre(student_logits: jax.Array,
                         teacher_avg_logits: jax.Array,
                         temperature: float = 1.0) -> jax.Array:
    """AVGLOGITS loss against PRE-AVERAGED teacher rows (the logit-bank
    fast path).  student: [..., V]; teacher_avg: [..., V] — e.g. bank rows
    gathered by sampled index; no [K, ..., V] tensor is materialized."""
    v = student_logits.shape[-1]
    s2 = student_logits.reshape(-1, v)
    t2 = teacher_avg_logits.reshape(-1, v)
    return _ensemble_kl_pre(s2, t2, temperature, 8, _interpret())


def ensemble_kl_loss_bank(student_logits: jax.Array, bank_rows: jax.Array,
                          scales, idx: jax.Array,
                          temperature: float = 1.0) -> jax.Array:
    """AVGLOGITS loss fused with the bank gather + dequantize.

    student: [..., V]; bank_rows: [N, V] in the bank's storage dtype
    (fp32 / bf16 / int8 / fp8); scales: per-ROW [N] fp32 dequant scales
    or None for unquantized banks; idx: [...] sampled bank indices.
    Dispatches exactly like :func:`ensemble_kl_loss_pre` (compiled on
    TPU, interpret elsewhere) — only the [B]-sized per-sample scale
    gather happens outside the kernel.
    """
    v = student_logits.shape[-1]
    s2 = student_logits.reshape(-1, v)
    idx2 = idx.reshape(-1)
    row_scale = (jnp.ones(idx2.shape, jnp.float32) if scales is None
                 else scales[idx2].astype(jnp.float32))
    return _ensemble_kl_bank(s2, bank_rows, row_scale, idx2, temperature,
                             _interpret())


@partial(jax.jit, static_argnames=("chunk",))
def ssd_scan(x, dt, a_log, bmat, cmat, chunk: int = 128):
    """Mamba2 SSD scan: x [B,S,H,P], dt [B,S,H], a_log [H], b/c [B,S,N]."""
    return _ssd(x, dt, a_log, bmat, cmat, chunk=chunk,
                interpret=_interpret())


@partial(jax.jit, static_argnames=("window", "block"))
def swa_attention(q, k, v, window: int | None = None, block: int = 128):
    """Flash sliding-window attention: q/k/v [B,H,S,D]."""
    return _swa(q, k, v, window, block=block, interpret=_interpret())
