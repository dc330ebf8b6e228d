"""Pallas TPU kernel for FedDF's AVGLOGITS distillation loss.

The fusion hot-loop evaluates KL(softmax(mean_k teacher), softmax(student))
over [K, B, V] logits with V up to 262 144 (gemma3).  Materialising the
averaged-probability tensors costs 3+ full [B, V] fp32 round-trips to HBM;
this kernel streams V in VMEM tiles with *online* logsumexp (flash-attention
style), producing per-row KL plus the two logsumexps (saved as residuals for
the backward kernel) in a single pass over the logits.

    KL_row = (St - Ss)/Z - lse_t + lse_s
      where, over v:  m  = max t̄_v          (running)
                      Z  = Σ e^{t̄_v - m}
                      St = Σ e^{t̄_v - m} t̄_v
                      Ss = Σ e^{t̄_v - m} s_v
                      lse_t = m + log Z ;  lse_s analogous.

Backward: d/ds = (softmax(s) - softmax(t̄)) * ḡ / B  — one more masked pass.

Grid: (B_tiles, V_tiles), V innermost/sequential; accumulators live in VMEM
scratch and persist across the V iterations of one B tile.

Three entry points share the kernels:

* :func:`ensemble_kl` — raw teachers [K, B, V]; the K axis is reduced to
  t̄ inside the kernel tile.
* :func:`ensemble_kl_pre` — PRE-AVERAGED teacher rows [B, V] (the
  teacher-logit-bank fast path, ``core/logit_bank.py``): bank rows stream
  through the same online-logsumexp pipeline with no [K, B, V]
  materialization anywhere.
* :func:`ensemble_kl_bank` — the WHOLE bank [N, V] (any storage dtype,
  fp32/bf16/int8/fp8) plus per-sample indices and dequant scales: gather,
  dequantize, log-softmax and KL are fused into one kernel via scalar-
  prefetch index maps, so neither the gathered nor the dequantized
  [B, V] teacher rows ever round-trip through HBM.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax import dtypes as jax_dtypes
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG = -1e30


def _teacher_tile(t_ref):
    """Teacher tile -> averaged [bB, bV] fp32 rows.  Rank-3 blocks carry
    the K teacher axis (AVGLOGITS reduces it here); rank-2 blocks are
    already-averaged logit-bank rows used as-is."""
    t = t_ref[...].astype(jnp.float32)
    return jnp.mean(t, axis=0) if t.ndim == 3 else t


def _pad_mask(vi, bv: int, v_total: int, shape):
    """True over the padded tail of the V axis for this tile."""
    v_idx = vi * bv + jax.lax.broadcasted_iota(jnp.int32, shape, 1)
    return v_idx >= v_total


def _init_row_stats(m_t, z_t, st_acc, ss_acc, m_s, z_s):
    m_t[...] = jnp.full_like(m_t, NEG)
    z_t[...] = jnp.zeros_like(z_t)
    st_acc[...] = jnp.zeros_like(st_acc)
    ss_acc[...] = jnp.zeros_like(ss_acc)
    m_s[...] = jnp.full_like(m_s, NEG)
    z_s[...] = jnp.zeros_like(z_s)


def _online_step(s, t, pad, m_t, z_t, st_acc, ss_acc, m_s, z_s):
    """One V tile of the flash-style running stats.  ``s``/``t`` are fp32
    [bB, bV] with the padded tail already pushed to NEG."""
    # --- online update for teacher stats
    m_new = jnp.maximum(m_t[...], jnp.max(t, axis=-1, keepdims=True))
    scale = jnp.exp(m_t[...] - m_new)
    e_t = jnp.exp(t - m_new)
    e_t = jnp.where(pad, 0.0, e_t)
    z_t[...] = z_t[...] * scale + jnp.sum(e_t, -1, keepdims=True)
    st_acc[...] = st_acc[...] * scale + jnp.sum(e_t * t, -1, keepdims=True)
    ss_acc[...] = ss_acc[...] * scale + jnp.sum(e_t * s, -1, keepdims=True)
    m_t[...] = m_new

    # --- online logsumexp for student
    ms_new = jnp.maximum(m_s[...], jnp.max(s, axis=-1, keepdims=True))
    e_s = jnp.exp(s - ms_new)
    e_s = jnp.where(pad, 0.0, e_s)
    z_s[...] = z_s[...] * jnp.exp(m_s[...] - ms_new) + jnp.sum(
        e_s, -1, keepdims=True)
    m_s[...] = ms_new


def _emit_row_stats(kl_ref, lse_t_ref, lse_s_ref,
                    m_t, z_t, st_acc, ss_acc, m_s, z_s):
    lse_t = m_t[...] + jnp.log(z_t[...])
    lse_s = m_s[...] + jnp.log(z_s[...])
    kl_ref[...] = (st_acc[...] - ss_acc[...]) / z_t[...] - lse_t + lse_s
    lse_t_ref[...] = lse_t
    lse_s_ref[...] = lse_s


def _fwd_kernel(s_ref, t_ref, kl_ref, lse_t_ref, lse_s_ref,
                m_t, z_t, st_acc, ss_acc, m_s, z_s, *, n_v_tiles: int,
                v_total: int, bv: int):
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        _init_row_stats(m_t, z_t, st_acc, ss_acc, m_s, z_s)

    s = s_ref[...].astype(jnp.float32)          # [bB, bV]
    t = _teacher_tile(t_ref)                    # [(K,)bB,bV] -> [bB,bV]

    pad = _pad_mask(vi, bv, v_total, s.shape)
    s = jnp.where(pad, NEG, s)
    t = jnp.where(pad, NEG, t)
    _online_step(s, t, pad, m_t, z_t, st_acc, ss_acc, m_s, z_s)

    @pl.when(vi == n_v_tiles - 1)
    def _finish():
        _emit_row_stats(kl_ref, lse_t_ref, lse_s_ref,
                        m_t, z_t, st_acc, ss_acc, m_s, z_s)


def _bwd_kernel(s_ref, t_ref, lse_t_ref, lse_s_ref, g_ref, ds_ref, *,
                v_total: int, bv: int, b_total: int):
    vi = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32)
    t = _teacher_tile(t_ref)
    pad = _pad_mask(vi, bv, v_total, s.shape)
    p_s = jnp.where(pad, 0.0, jnp.exp(s - lse_s_ref[...]))
    p_t = jnp.where(pad, 0.0, jnp.exp(t - lse_t_ref[...]))
    g = g_ref[0]
    ds_ref[...] = ((p_s - p_t) * (g / b_total)).astype(ds_ref.dtype)


# ---------------------------------------------------------------------------
# fused bank kernels: gather-by-index + dequantize + log-softmax + KL
# ---------------------------------------------------------------------------
#
# Grid (B, n_v), one sampled row per grid row: the sampled index vector
# rides in as a SCALAR-PREFETCH operand, so the bank's BlockSpec index map
# ``lambda i, j, idx_ref: (idx_ref[i], 0, j)`` DMAs exactly the sampled
# bank row for grid row i — the gathered [B, V] teacher tensor (let alone
# its dequantized fp32 copy) never exists in HBM.  Student and bank are
# viewed as [rows, 1, V] (a free reshape) so each block's trailing two
# dims (1, bV) equal the array's (1, V) — the TPU tiling rule a (1, bV)
# block of an [N, V] array breaks.  The per-row scales sit whole in SMEM.
# Quantized rows dequantize in-register: ``t = t_tile * (scale_row / T)``;
# fp32/bf16 banks pass scale 1.  The student's 1/T fold also happens
# in-tile (temperature is a static nondiff arg), so there is no [B, V]
# pre-scaling pass either.

def _bank_fwd_kernel(idx_ref, s_ref, t_ref, sc_ref,
                     kl_ref, lse_t_ref, lse_s_ref,
                     m_t, z_t, st_acc, ss_acc, m_s, z_s, *,
                     n_v_tiles: int, v_total: int, bv: int, inv_t: float):
    del idx_ref  # consumed by the BlockSpec index maps
    vi = pl.program_id(1)

    @pl.when(vi == 0)
    def _init():
        _init_row_stats(m_t, z_t, st_acc, ss_acc, m_s, z_s)

    s = s_ref[...].astype(jnp.float32) * inv_t          # [1, bV]
    t = t_ref[...].astype(jnp.float32) * (sc_ref[pl.program_id(0)] * inv_t)

    pad = _pad_mask(vi, bv, v_total, s.shape)
    s = jnp.where(pad, NEG, s)
    t = jnp.where(pad, NEG, t)
    _online_step(s, t, pad, m_t, z_t, st_acc, ss_acc, m_s, z_s)

    @pl.when(vi == n_v_tiles - 1)
    def _finish():
        _emit_row_stats(kl_ref, lse_t_ref, lse_s_ref,
                        m_t, z_t, st_acc, ss_acc, m_s, z_s)


def _bank_bwd_kernel(idx_ref, s_ref, t_ref, sc_ref, lse_t_ref, lse_s_ref,
                     g_ref, ds_ref, *, v_total: int, bv: int, b_total: int,
                     inv_t: float):
    del idx_ref
    vi = pl.program_id(1)
    s = s_ref[...].astype(jnp.float32) * inv_t
    t = t_ref[...].astype(jnp.float32) * (sc_ref[pl.program_id(0)] * inv_t)
    pad = _pad_mask(vi, bv, v_total, s.shape)
    p_s = jnp.where(pad, 0.0, jnp.exp(s - lse_s_ref[...]))
    p_t = jnp.where(pad, 0.0, jnp.exp(t - lse_t_ref[...]))
    g = g_ref[0]
    ds_ref[...] = ((p_s - p_t) * (g / b_total)).astype(ds_ref.dtype)


def _pad_to(x, mult, axis, value=0.0):
    pad = (-x.shape[axis]) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths, constant_values=value)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def ensemble_kl(student_logits, teacher_logits, temperature: float = 1.0,
                block_b: int = 8, interpret: bool = True):
    loss, _ = _fwd(student_logits, teacher_logits, temperature, block_b,
                   interpret)
    return loss


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def ensemble_kl_pre(student_logits, teacher_avg_logits,
                    temperature: float = 1.0, block_b: int = 8,
                    interpret: bool = True):
    """AVGLOGITS loss against pre-averaged teacher rows [B, V] (logit-bank
    fast path); numerically identical to :func:`ensemble_kl` fed the
    un-averaged [K, B, V] teachers whose mean these rows are."""
    loss, _ = _fwd(student_logits, teacher_avg_logits, temperature, block_b,
                   interpret)
    return loss


def _block_v(v: int) -> int:
    # V tile: multiple of 128 lanes, bounded by VMEM budget
    return min(2048, max(128, 128 * ((v + 127) // 128)))


def _row_spec(bb: int):
    """Per-row statistics ([rows, 1] fp32) by B tile: a 2-D block, since
    the TPU refuses rank-1 blocks that are neither the whole array nor a
    multiple of 128."""
    return pl.BlockSpec((bb, 1), lambda i, j: (i, 0))


def _pad_teacher(t, bb, bv):
    """Pad [B, V] (pre-averaged) or [K, B, V] teachers + their BlockSpec."""
    if t.ndim == 2:
        return (_pad_to(_pad_to(t, bb, 0), bv, 1),
                pl.BlockSpec((bb, bv), lambda i, j: (i, j)))
    k = t.shape[0]
    return (_pad_to(_pad_to(t, bb, 1), bv, 2),
            pl.BlockSpec((k, bb, bv), lambda i, j: (0, i, j)))


def _fwd(student_logits, teacher_logits, temperature, block_b, interpret):
    b, v = student_logits.shape
    s = student_logits / temperature
    t = teacher_logits / temperature

    bv = _block_v(v)
    bb = min(block_b, b)
    s_p = _pad_to(_pad_to(s, bb, 0), bv, 1)
    t_p, t_spec = _pad_teacher(t, bb, bv)
    bp, vp = s_p.shape
    n_b, n_v = bp // bb, vp // bv

    kern = functools.partial(_fwd_kernel, n_v_tiles=n_v, v_total=v, bv=bv)
    kl, lse_t, lse_s = pl.pallas_call(
        kern,
        grid=(n_b, n_v),
        in_specs=[
            pl.BlockSpec((bb, bv), lambda i, j: (i, j)),
            t_spec,
        ],
        out_specs=[_row_spec(bb)] * 3,
        scratch_shapes=[pltpu.VMEM((bb, 1), jnp.float32)] * 6,
        out_shape=[jax.ShapeDtypeStruct((bp, 1), jnp.float32)] * 3,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(s_p, t_p)
    loss = jnp.sum(kl[:b]) / b * temperature ** 2
    return loss, (student_logits, teacher_logits, lse_t, lse_s)


def _fwd_rule(student_logits, teacher_logits, temperature, block_b,
              interpret):
    return _fwd(student_logits, teacher_logits, temperature, block_b,
                interpret)


def _bwd_rule(temperature, block_b, interpret, res, g):
    student_logits, teacher_logits, lse_t, lse_s = res
    b, v = student_logits.shape
    s = student_logits / temperature
    t = teacher_logits / temperature

    bv = _block_v(v)
    bb = min(block_b, b)
    s_p = _pad_to(_pad_to(s, bb, 0), bv, 1)
    t_p, t_spec = _pad_teacher(t, bb, bv)
    bp, vp = s_p.shape
    n_b, n_v = bp // bb, vp // bv

    kern = functools.partial(_bwd_kernel, v_total=v, bv=bv, b_total=b)
    g_arr = jnp.asarray([g * temperature], jnp.float32)  # T^2 / T = T
    ds = pl.pallas_call(
        kern,
        grid=(n_b, n_v),
        in_specs=[
            pl.BlockSpec((bb, bv), lambda i, j: (i, j)),
            t_spec,
            _row_spec(bb),
            _row_spec(bb),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bb, bv), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((bp, vp), student_logits.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(s_p, t_p, lse_t, lse_s, g_arr)
    return ds[:b, :v], None


ensemble_kl.defvjp(_fwd_rule, _bwd_rule)
ensemble_kl_pre.defvjp(_fwd_rule, _bwd_rule)


def _zero_cotangent(x):
    """Cotangent for a non-differentiated primal: symbolic float0 zeros
    for integer args (idx, int8 bank rows), same-dtype zeros for inexact
    ones (DCE'd under jit — nothing consumes them)."""
    if jnp.issubdtype(x.dtype, jnp.inexact):
        return jnp.zeros(x.shape, x.dtype)
    return np.zeros(x.shape, jax_dtypes.float0)


@functools.partial(jax.custom_vjp, nondiff_argnums=(4, 5))
def ensemble_kl_bank(student_logits, bank_rows, row_scale, idx,
                     temperature: float = 1.0, interpret: bool = True):
    """AVGLOGITS loss straight off a resident logit bank.

    student_logits: [B, V] (differentiable); bank_rows: [N, V] in any
    bank storage dtype (fp32 / bf16 / int8 / fp8); row_scale: [B] fp32
    dequant scale PER SAMPLED ROW (``scales[idx]``, or ones for
    unquantized banks); idx: [B] int row indices into the bank.
    Equals ``ensemble_kl_pre(student, dequant(bank_rows[idx]))`` without
    ever materializing the gathered or dequantized [B, V] rows.
    """
    loss, _ = _bank_fwd(student_logits, bank_rows, row_scale, idx,
                        temperature, interpret)
    return loss


def _bank_block_v(v: int) -> int:
    """V tile of the bank kernels.  The bank is never padded (that would
    copy it), so a tile is either the whole row or 2048 lanes with a
    masked partial tail tile."""
    return v if v <= 2048 else 2048


_SQ = pl.Squeezed()


def _bank_call(kernel, b: int, n_v: int, bv: int, extra_in_specs, out_specs,
               out_shape, scratch_shapes, interpret: bool):
    """pallas_call shared by the bank fwd/bwd: grid (B, n_v); student row
    blocks by grid row, bank row blocks by the PREFETCHED sampled index,
    per-row scales whole in SMEM."""
    in_specs = [
        pl.BlockSpec((_SQ, 1, bv), lambda i, j, idx_ref: (i, 0, j)),
        pl.BlockSpec((_SQ, 1, bv), lambda i, j, idx_ref: (idx_ref[i], 0, j)),
        pl.BlockSpec(memory_space=pltpu.SMEM),
    ] + list(extra_in_specs)
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(b, n_v),
            in_specs=in_specs,
            out_specs=out_specs,
            scratch_shapes=scratch_shapes,
        ),
        out_shape=out_shape,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )


_BANK_ROW_SPEC = pl.BlockSpec((_SQ, 1, 1), lambda i, j, idx_ref: (i, 0, 0))


def _rows3(x):
    """[R, V] -> the [R, 1, V] view the bank kernels block over."""
    return x.reshape(x.shape[0], 1, x.shape[1])


def _bank_fwd(student_logits, bank_rows, row_scale, idx, temperature,
              interpret):
    b, v = student_logits.shape
    bv = _bank_block_v(v)
    n_v = -(-v // bv)
    kern = functools.partial(_bank_fwd_kernel, n_v_tiles=n_v, v_total=v,
                             bv=bv, inv_t=1.0 / temperature)
    kl, lse_t, lse_s = _bank_call(
        kern, b, n_v, bv, (),
        out_specs=[_BANK_ROW_SPEC] * 3,
        out_shape=[jax.ShapeDtypeStruct((b, 1, 1), jnp.float32)] * 3,
        scratch_shapes=[pltpu.VMEM((1, 1), jnp.float32)] * 6,
        interpret=interpret,
    )(idx.astype(jnp.int32), _rows3(student_logits), _rows3(bank_rows),
      row_scale.astype(jnp.float32))
    loss = jnp.sum(kl) / b * temperature ** 2
    return loss, (student_logits, bank_rows, row_scale, idx, lse_t, lse_s)


def _bank_fwd_rule(student_logits, bank_rows, row_scale, idx, temperature,
                   interpret):
    return _bank_fwd(student_logits, bank_rows, row_scale, idx,
                     temperature, interpret)


def _bank_bwd_rule(temperature, interpret, res, g):
    student_logits, bank_rows, row_scale, idx, lse_t, lse_s = res
    b, v = student_logits.shape
    bv = _bank_block_v(v)
    n_v = -(-v // bv)
    kern = functools.partial(_bank_bwd_kernel, v_total=v, bv=bv, b_total=b,
                             inv_t=1.0 / temperature)
    g_arr = jnp.asarray([g * temperature], jnp.float32)  # T^2 / T = T
    ds = _bank_call(
        kern, b, n_v, bv,
        (_BANK_ROW_SPEC, _BANK_ROW_SPEC,
         pl.BlockSpec(memory_space=pltpu.SMEM)),
        out_specs=pl.BlockSpec((_SQ, 1, bv),
                               lambda i, j, idx_ref: (i, 0, j)),
        out_shape=jax.ShapeDtypeStruct((b, 1, n_v * bv),
                                       student_logits.dtype),
        scratch_shapes=(),
        interpret=interpret,
    )(idx.astype(jnp.int32), _rows3(student_logits), _rows3(bank_rows),
      row_scale.astype(jnp.float32), lse_t, lse_s, g_arr)
    return (ds[:, 0, :v], _zero_cotangent(bank_rows),
            _zero_cotangent(row_scale), _zero_cotangent(idx))


ensemble_kl_bank.defvjp(_bank_fwd_rule, _bank_bwd_rule)
