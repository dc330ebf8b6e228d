"""Teacher-logit bank: the precomputed, shared, device-resident fast path
for FedDF's server-side distillation.

FedDF's cost center is the fusion loop — up to 10k Adam steps per round
where every step re-forwards *all K frozen teachers* on the distillation
batch, and in the heterogeneous case every one of the G group-students
redundantly re-forwards the same all-groups teacher ensemble.  But the
teachers are FROZEN during fusion and AVGLOGITS only ever consumes
``mean_k f(x_k, d)``: for a source with a finite pool (``DistillSource.
pool()``), the per-example averaged teacher logits can be computed ONCE —
one chunked vmapped forward pass per teacher group over the pool, reduced
on the fly to ``[N, C]`` — and the scan then *gathers* bank rows by the
sampled indices instead of calling the teachers per step:

    teacher forwards:  K x steps            ->  K x ceil(N / chunk)
    heterogeneous:     G x K x steps        ->  K x ceil(N / chunk)   (shared)

Memory: ``N x C x itemsize(bank_dtype)`` bytes, plus one fp32 scale per
row for the quantized dtypes (fp32 default; bf16 halves the rows; int8 /
fp8_e4m3 shrink them 4x to ``N x C x 1 + N x 4`` with per-row symmetric
scales computed during the build pass — the fused distill kernel
dequantizes rows on the fly, see ``kernels/ensemble_kl.ensemble_kl_bank``).
The bank lives on device next to its pool; pass a ``sharding`` to spread
the N axis over a mesh.  See docs/distill_fast_path.md for the lifecycle
and the break-even analysis against the on-the-fly path.
"""
from __future__ import annotations

import dataclasses
import functools
import time
import warnings
import weakref
from typing import Callable, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp

from repro.common.counters import TraceCounter
from repro.common.pytree import tree_leading_dim
from repro.obs import trace as _trace
from repro.obs.metrics import REGISTRY
from repro.common.options import (BANK_DTYPES, LOGIT_BANK_MODES,
                                  QUANTIZED_BANK_DTYPES)

DEFAULT_CHUNK = 512

# symmetric per-row quantization: q = round/cast(row / scale) with
# scale = amax(|row|) / QUANT_MAX[dtype], so the row's extremes land
# exactly on the representable range
_INT8_MAX = 127.0
_FP8_E4M3_MAX = 448.0  # largest finite float8_e4m3fn value


def _storage_dtypes():
    out = {"float32": jnp.float32, "bfloat16": jnp.bfloat16,
           "int8": jnp.int8}
    fp8 = getattr(jnp, "float8_e4m3fn", None)
    if fp8 is not None:  # backend/jax support is optional
        out["fp8_e4m3"] = fp8
    return out


_BANK_DTYPES = _storage_dtypes()
_QUANT_MAX = {"int8": _INT8_MAX, "fp8_e4m3": _FP8_E4M3_MAX}

# kept under the historic name: feddf.py (CHUNK_COMPILES) and downstream
# code construct counters via this alias
_ForwardCounter = TraceCounter

# Process-wide count of teacher *batch* forwards (one teacher, one batch
# of rows) — the bench/tests' evidence that the bank removes the K x steps
# (and hetero G x) redundancy.  Lives in the unified metrics registry
# under a dotted name; this alias keeps the historic interface.
TEACHER_FORWARDS = REGISTRY.counter("core.logit_bank.teacher_forwards")

# Counts TRACES of the bank's forward program: a trace-time side effect,
# as ``feddf.CHUNK_COMPILES``, so it moves only when jax re-traces — once
# per run when the teacher stacks cross as arguments, once per build when
# a plain callable keeps the closure path.
BANK_COMPILES = REGISTRY.counter("core.logit_bank.bank_compiles")

# Cross-round bank forwards, weakly keyed by the first teacher Net (the
# idiom of ``feddf._CHUNK_CACHE``).  Values close over the teachers'
# apply fns, never a Net: a value that referenced its weak key would pin
# the entry forever (core/client.py's eval caches).
_FWD_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


@dataclasses.dataclass
class LogitBank:
    """Per-round bank of averaged teacher logits over a distillation pool.

    ``pool``: device-resident inputs [N, ...]; ``logits``: mean-over-all-
    teachers logits [N, C] in ``bank_dtype``.  Built once per round (and
    shared by every group-student in heterogeneous fusion); discarded when
    the round's fused models are done.
    """

    pool: jax.Array
    logits: jax.Array
    n_teachers: int
    n_teacher_batch_forwards: int
    build_time_s: float
    # per-row fp32 dequantization scales [N] for the quantized dtypes
    # (int8 / fp8_e4m3); None for float32 / bfloat16 rows
    scales: Optional[jax.Array] = None
    # the FusionConfig.bank_dtype literal these rows are stored in
    dtype_name: str = "float32"
    # True when these rows came out of the persistent cross-round cache
    # (static teacher pool) instead of a fresh build — callers charge zero
    # build forwards for a reused bank
    reused: bool = False

    @property
    def n(self) -> int:
        return int(self.pool.shape[0])

    @property
    def quantized(self) -> bool:
        return self.scales is not None

    @property
    def nbytes(self) -> int:
        """Bank row bytes, scales included — the observable the quantized
        dtypes exist to shrink (N x C x 1 + N x 4 vs N x C x 4)."""
        total = int(self.logits.size) * self.logits.dtype.itemsize
        if self.scales is not None:
            total += int(self.scales.size) * self.scales.dtype.itemsize
        return total


def bank_dtype(name: str):
    """Storage jnp dtype for a ``FusionConfig.bank_dtype`` literal.  Raises
    for unknown names, and for ``fp8_e4m3`` when this jax build has no
    float8 support (the literal itself is always spec-valid)."""
    if name in BANK_DTYPES and name not in _BANK_DTYPES:
        raise ValueError(
            f"bank_dtype {name!r} is not supported by this jax build "
            f"(no jnp.float8_e4m3fn); use one of {sorted(_BANK_DTYPES)}")
    if name not in _BANK_DTYPES:
        raise ValueError(f"bank_dtype must be one of "
                         f"{sorted(BANK_DTYPES)}, got {name!r}")
    return _BANK_DTYPES[name]


def quantize_rows(rows: jax.Array, dtype_name: str
                  ) -> Tuple[jax.Array, jax.Array]:
    """Symmetric per-row quantization of fp32 logit rows ``[M, C]`` ->
    ``(q [M, C] storage-dtype, scales [M] fp32)``.

    ``scale_i = amax(|row_i|) / qmax`` maps each row's extremes onto the
    full representable range, so the worst-case dequant error is bounded
    per row (int8: ``scale_i / 2`` from rounding).  All-zero rows get
    scale 1 so dequantization is exact.  KL is shift-invariant in the
    logits but NOT scale-invariant, which is why the scale must ride
    along instead of being folded into a global constant.
    """
    qmax = _QUANT_MAX[dtype_name]
    storage = bank_dtype(dtype_name)
    rows = rows.astype(jnp.float32)
    amax = jnp.max(jnp.abs(rows), axis=-1)
    scales = jnp.where(amax > 0, amax / qmax, 1.0)
    scaled = rows / scales[:, None]
    if dtype_name == "int8":
        q = jnp.clip(jnp.round(scaled), -_INT8_MAX, _INT8_MAX)
    else:  # fp8: the cast itself rounds; clip guards the finite range
        q = jnp.clip(scaled, -qmax, qmax)
    return q.astype(storage), scales


def dequantize_rows(rows: jax.Array,
                    scales: Optional[jax.Array] = None) -> jax.Array:
    """fp32 logit rows from stored bank rows (+ their per-row scales)."""
    out = rows.astype(jnp.float32)
    if scales is not None:
        out = out * scales[..., None]
    return out


def _dtype_name_of(dtype) -> str:
    """Normalize a ``dtype`` argument (BANK_DTYPES literal or the jnp
    dtype itself — the historic calling convention) to the literal."""
    if isinstance(dtype, str):
        bank_dtype(dtype)  # validate
        return dtype
    for name, jdt in _BANK_DTYPES.items():
        if jnp.dtype(dtype) == jnp.dtype(jdt):
            return name
    raise ValueError(f"unsupported bank dtype {dtype!r}; "
                     f"use one of {sorted(_BANK_DTYPES)}")


def stacked_teacher_count(teacher_logit_fns: Sequence[Callable]
                          ) -> Optional[int]:
    """Total K over teacher fns that all carry a stamped ``.net`` and
    ``.stack`` (``feddf.make_teacher_logits_fn``), read off the stacks;
    None when any is a plain callable, whose K only a trace can tell."""
    if not teacher_logit_fns or not all(
            hasattr(f, "net") and hasattr(f, "stack")
            for f in teacher_logit_fns):
        return None
    return sum(tree_leading_dim(f.stack) for f in teacher_logit_fns)


def _reduce_rows(t, w_norm, dtype_name: str):
    """[K, c, C] teacher logits -> (stored rows [c, C], scales or None):
    the fp32 uniform mean (or ``w_norm``-weighted consensus), quantized
    per row for the quantized dtypes."""
    t = t.astype(jnp.float32)
    mean = (jnp.mean(t, axis=0) if w_norm is None
            else jnp.tensordot(w_norm, t, axes=([0], [0])))
    if dtype_name in QUANTIZED_BANK_DTYPES:
        return quantize_rows(mean, dtype_name)
    return mean.astype(bank_dtype(dtype_name)), None


def _stacked_fwd(nets: Sequence, dtype_name: str, weighted: bool):
    """The cross-round cached ``fwd(stacks, w_norm, xc)`` for these
    teacher nets: the stacks (and weights) are ARGUMENTS, so round t+1's
    fresh uploads reuse round t's program instead of folding their
    weights into a new one; jax's own signature cache takes new shapes
    (another K, chunk or pool width)."""
    per = _FWD_CACHE.get(nets[0])
    if per is None:
        per = {}
        _FWD_CACHE[nets[0]] = per
    applies = tuple(n.apply for n in nets)
    # the apply fns themselves, not their nets' ids: the entry holds
    # them, so no id recycled after a net dies can alias this program
    key = (applies, dtype_name, weighted)
    fwd = per.get(key)
    if fwd is None:
        def fwd(stacks, w_norm, xc):
            BANK_COMPILES.add(1)  # trace-time side effect: counts compiles

            def logits(apply, stack):
                return jax.vmap(lambda p: apply(p, xc, train=False))(stack)

            t = jnp.concatenate(
                [logits(a, s) for a, s in zip(applies, stacks)], axis=0)
            return _reduce_rows(t, w_norm if weighted else None, dtype_name)

        fwd = jax.jit(fwd)
        per[key] = fwd
    return fwd


def build_logit_bank(teacher_logit_fns: Sequence[Callable], pool, *,
                     chunk_size: int = DEFAULT_CHUNK, dtype=jnp.float32,
                     sharding=None, teacher_weights=None) -> LogitBank:
    """One chunked pass of every teacher group over ``pool`` -> LogitBank.

    Each chunk evaluates all groups' stacked teachers ([K_g, c, C] each),
    concatenates along the teacher axis and reduces to the fp32 mean on
    the fly — the full [K, N, C] tensor is never materialized.  With
    ``dtype=float32`` the stored rows are the exact values the on-the-fly
    path would have averaged per step, so trajectories match.  For the
    quantized dtypes (``int8`` / ``fp8_e4m3``, by literal name or storage
    jnp dtype) each chunk's fp32 mean is quantized inside the same jitted
    pass — per-row scales ride on ``LogitBank.scales`` and the full fp32
    bank never materializes either.

    ``teacher_weights`` ([k_total] in concat order; normalized or not —
    it is re-normalized here) folds a weighted teacher consensus into the
    stored rows at build time (the buffered-async staleness-importance
    path, docs/population.md): downstream gathers stay byte-identical in
    shape and cost.  None keeps the historic uniform mean bitwise.

    Stamped teacher fns (``.net`` / ``.stack``) run one cross-round
    cached program that takes the stacks and weights as arguments
    (:func:`_stacked_fwd`); plain callables are closed over and re-jitted
    per build.
    """
    t0 = time.time()
    dtype_name = _dtype_name_of(dtype)
    pool = jnp.asarray(pool)
    n = int(pool.shape[0])
    c = max(1, min(int(chunk_size), n))
    n_chunks = -(-n // c)
    pad = n_chunks * c - n
    pool_p = (jnp.concatenate(
        [pool, jnp.zeros((pad,) + pool.shape[1:], pool.dtype)])
        if pad else pool)

    k_total = stacked_teacher_count(teacher_logit_fns)
    stacked = k_total is not None
    if not stacked:
        k_total = int(jax.eval_shape(
            lambda xc: jnp.concatenate(
                [jnp.asarray(f(xc)) for f in teacher_logit_fns], axis=0),
            jax.ShapeDtypeStruct((c,) + pool.shape[1:], pool.dtype)
        ).shape[0])

    w_norm = None
    if teacher_weights is not None:
        w = jnp.asarray(teacher_weights, jnp.float32)
        if w.shape != (k_total,):
            raise ValueError(
                f"teacher_weights must have shape ({k_total},) to match "
                f"the concatenated teacher axis, got {tuple(w.shape)}")
        w_norm = w / jnp.sum(w)

    if stacked:
        fwd = functools.partial(
            _stacked_fwd([f.net for f in teacher_logit_fns], dtype_name,
                         w_norm is not None),
            tuple(f.stack for f in teacher_logit_fns), w_norm)
    else:
        @jax.jit
        def fwd(xc):
            BANK_COMPILES.add(1)  # trace-time side effect: counts compiles
            t = jnp.concatenate(
                [jnp.asarray(f(xc)) for f in teacher_logit_fns], axis=0)
            return _reduce_rows(t, w_norm, dtype_name)

    chunks, scale_chunks = [], []
    for i in range(n_chunks):
        rows, sc = fwd(pool_p[i * c:(i + 1) * c])
        chunks.append(rows)
        if sc is not None:
            scale_chunks.append(sc)
        TEACHER_FORWARDS.add(k_total)
    logits = (jnp.concatenate(chunks, axis=0)[:n] if n_chunks > 1
              else chunks[0][:n])
    scales = None
    if scale_chunks:
        scales = (jnp.concatenate(scale_chunks, axis=0)[:n]
                  if n_chunks > 1 else scale_chunks[0][:n])
    if sharding is not None:
        pool = jax.device_put(pool, sharding)
        logits = jax.device_put(logits, sharding)
        if scales is not None:
            scales = jax.device_put(scales, sharding)
    return LogitBank(pool=pool, logits=logits, n_teachers=k_total,
                     n_teacher_batch_forwards=n_chunks * k_total,
                     build_time_s=time.time() - t0,
                     scales=scales, dtype_name=dtype_name)


class _PersistentBankCache:
    """Size-1 cross-round bank cache for STATIC teacher pools.

    Keyed on teacher-stack *identity* (the ``id()`` of every stacked
    teacher leaf plus the pool object and bank dtype): when the exact
    same frozen teacher arrays are fused again — e.g. repeated
    ``feddf_init_from='previous'`` ablation sweeps or benchmarks
    re-fusing one round's uploads — the previous build's rows are reused
    instead of re-forwarding every teacher over the pool.  Any upload
    change produces new arrays, hence new ids, hence a miss that
    replaces the entry.

    The keyed arrays are held through WEAK references: a hit requires
    every one of them to still be alive, so a recycled id can never
    produce a false hit, and an ordinary training run — whose uploads
    die as soon as the next round replaces them — drops the entry (bank
    rows included, via the death callbacks) instead of pinning a whole
    round's working set for process lifetime.
    """

    def __init__(self):
        self._gen = 0
        self._key = None
        self._refs: Tuple = ()
        self._bank: Optional[LogitBank] = None

    def lookup(self, key) -> Optional[LogitBank]:
        if key is None or key != self._key:
            return None
        if any(r() is None for r in self._refs):
            self.clear()  # a keyed array died; its id may be recycled
            return None
        return self._bank

    def store(self, key, referents, bank: LogitBank) -> None:
        self._gen += 1
        gen = self._gen

        def on_dead(_ref, _gen=gen):
            # drop the bank as soon as any keyed upload is GC'd — unless
            # a newer entry (or clear) already superseded this one
            if self._gen == _gen:
                self.clear()

        self._key = key
        self._refs = tuple(weakref.ref(x, on_dead) for x in referents)
        self._bank = bank

    def clear(self) -> None:
        self._gen += 1
        self._key, self._refs, self._bank = None, (), None


PERSISTENT_BANK = _PersistentBankCache()


def _identity_key(teacher_logit_fns, pool, dtype_name: str,
                  teacher_weights=None):
    """(key, referents) for the persistent cache, or (None, ()) when any
    teacher fn is a plain callable without a stamped ``.stack`` (no
    stable identity to key on).  Teacher weights join the key by VALUE:
    the same frozen stacks re-fused under different staleness importance
    must not hit the uniform (or differently-weighted) entry."""
    ids, referents = [], []
    for f in teacher_logit_fns:
        stack = getattr(f, "stack", None)
        if stack is None:
            return None, ()
        leaves = jax.tree.leaves(stack)
        ids.extend(id(l) for l in leaves)
        referents.extend(leaves)
    referents.append(pool)
    w_key = (None if teacher_weights is None
             else tuple(float(w) for w in jnp.asarray(teacher_weights)))
    return (tuple(ids), id(pool), dtype_name, w_key), referents


def resolve_bank(teacher_logit_fns: Sequence[Callable], source, fusion, *,
                 sharding=None, expected_steps: Optional[int] = None,
                 teacher_weights=None
                 ) -> Tuple[Optional[LogitBank], str]:
    """Resolve ``FusionConfig.logit_bank`` against the source.

    Returns ``(bank_or_None, reason)`` where ``reason`` is one of
    ``built`` / ``reused`` (persistent-cache hit) / ``off`` /
    ``no_teachers`` / ``no_pool`` / ``skipped_small_run``.

    ``auto`` builds a bank whenever the source exposes a pool AND the run
    is long enough to amortize the build: with ``expected_steps`` given
    (the caller's early-stopping estimate), a run expected to touch fewer
    than ``N`` pool rows (``expected_steps x batch_size < N``) keeps the
    on-the-fly path — the bank's one full pass over the pool would cost
    more teacher forwards than it saves.  ``on`` always builds when it
    can and warns when it cannot (generator / noise synthesize inputs per
    step, so there is nothing to precompute over).
    """
    mode = getattr(fusion, "logit_bank", "off")
    if mode not in LOGIT_BANK_MODES:
        raise ValueError(f"logit_bank must be one of {LOGIT_BANK_MODES}, "
                         f"got {mode!r}")
    if mode == "off":
        return None, "off"
    if not teacher_logit_fns:
        return None, "no_teachers"
    pool_fn = getattr(source, "pool", None)
    pool = pool_fn() if callable(pool_fn) else None
    if pool is None:
        if mode == "on":
            warnings.warn(
                f"logit_bank='on' but source {type(source).__name__} has "
                f"no indexable pool(); falling back to on-the-fly teacher "
                f"forwards", UserWarning, stacklevel=2)
        return None, "no_pool"
    dtype_name = fusion.bank_dtype
    bank_dtype(dtype_name)  # validate before any early-out
    key, referents = (None, ()) if sharding is not None else \
        _identity_key(teacher_logit_fns, pool, dtype_name,
                      teacher_weights)
    # cache lookup precedes the break-even skip: a cached bank costs one
    # dict compare, so even a run too short to amortize a BUILD uses it
    cached = PERSISTENT_BANK.lookup(key)
    if cached is not None:
        with _trace.span("bank_reuse", pool_n=len(pool)):
            return dataclasses.replace(cached, reused=True), "reused"
    if (mode == "auto" and expected_steps is not None
            and expected_steps * fusion.batch_size < len(pool)):
        return None, "skipped_small_run"
    with _trace.span("bank_build", pool_n=len(pool),
                     n_teachers=len(teacher_logit_fns)):
        bank = build_logit_bank(teacher_logit_fns, pool,
                                dtype=bank_dtype(dtype_name),
                                sharding=sharding,
                                teacher_weights=teacher_weights)
    if key is not None:
        PERSISTENT_BANK.store(key, referents, bank)
    return bank, "built"


def bank_for_fusion(teacher_logit_fns: Sequence[Callable], source,
                    fusion, *, sharding=None,
                    expected_steps: Optional[int] = None
                    ) -> Optional[LogitBank]:
    """:func:`resolve_bank` without the reason (the historic surface)."""
    return resolve_bank(teacher_logit_fns, source, fusion,
                        sharding=sharding,
                        expected_steps=expected_steps)[0]
