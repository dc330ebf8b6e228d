"""FedDF ensemble-distillation model fusion (the paper's core contribution).

AVGLOGITS (paper eq. in §3):

    x_{t,j} = x_{t,j-1} - eta * d/dx KL( sigma(mean_k f(x_k, d)),
                                         sigma(f(x_{t,j-1}, d)) )

Implementation notes:

* Teachers of one prototype are stacked along a leading "clients" axis and
  evaluated with a single ``jax.vmap``-ed forward — one fused program per
  prototype instead of |S_t| sequential forwards.
* Teachers are FROZEN during fusion, so for sources with a finite pool the
  averaged teacher logits are precomputed ONCE into a device-resident
  **logit bank** (``core/logit_bank.py``) and the scan *gathers* bank rows
  by the sampled indices instead of re-forwarding the K teachers per step
  (K×steps forwards → K×(N/chunk)); heterogeneous fusion builds the bank
  once and shares it across all G group-students.  ``FusionConfig.
  logit_bank`` controls this (``auto``/``on``/``off``); generator / noise
  sources have no pool and keep the on-the-fly path.
* The student update runs in jit'd chunks of ``eval_every`` steps
  (lax.scan) with ``params``/``opt_state`` donated where the backend
  supports it; between chunks a jitted validation pass tracks
  best-params / patience ON DEVICE (``lax.cond`` keep/replace — only
  scalar accuracies cross to the host), implementing the paper's early
  stopping (plateau patience 1e3 steps, cap 1e4, Adam lr 1e-3 with cosine
  annealing — §4.1 "model fusion procedure").
* The distillation batch is drawn inside the scan from the
  :class:`~repro.data.distill_sources.DistillSource` (unlabeled data /
  generator / noise), keyed by a threaded PRNG; the bank path draws the
  *same indices* via ``source.sample_indices``, so both trajectories
  match.
* ``use_fused_kernel`` routes the loss through the Pallas ``ensemble_kl``
  kernel: ``True`` always, ``"auto"`` (default) on TPU only.  The bank
  path uses the pre-averaged variant that streams [B, V] bank rows.
"""
from __future__ import annotations

import dataclasses
import weakref
from typing import Callable, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.common.pytree import (tree_isfinite, tree_leading_dim, tree_stack,
                                 tree_weighted_mean_stacked)
from repro.common.sharding import donation_supported
from repro.obs import trace as _trace
from repro.obs.metrics import REGISTRY
from repro.core.logit_bank import (TEACHER_FORWARDS, LogitBank,
                                   _ForwardCounter, dequantize_rows,
                                   resolve_bank, stacked_teacher_count)
from repro.core.nets import Net
from repro.data.distill_sources import DistillSource
from repro.optim.optimizers import adam, apply_updates
from repro.optim.schedules import cosine


def avg_logits_kl_pre(student_logits: jax.Array,
                      teacher_avg_logits: jax.Array,
                      temperature: float = 1.0) -> jax.Array:
    """KL( softmax(teacher_avg), softmax(student) ), mean over batch.

    teacher_avg_logits: [B, C] already averaged over teachers (logit-bank
    rows); student_logits: [B, C].
    """
    t = teacher_avg_logits.astype(jnp.float32) / temperature
    s = student_logits.astype(jnp.float32) / temperature
    logp_t = jax.nn.log_softmax(t, axis=-1)
    logp_s = jax.nn.log_softmax(s, axis=-1)
    p_t = jnp.exp(logp_t)
    kl = jnp.sum(p_t * (logp_t - logp_s), axis=-1)
    return jnp.mean(kl) * temperature ** 2


def avg_logits_kl(student_logits: jax.Array, teacher_logits: jax.Array,
                  temperature: float = 1.0,
                  teacher_weights: Optional[jax.Array] = None) -> jax.Array:
    """KL( softmax(mean_k teacher), softmax(student) ), mean over batch.

    teacher_logits: [K, B, C] (raw, un-averaged); student_logits: [B, C].
    ``teacher_weights`` ([K], normalized) replaces the uniform mean with a
    weighted consensus — the FedAsync staleness-importance path
    (docs/population.md); None keeps the historic uniform mean bitwise.
    """
    t = teacher_logits.astype(jnp.float32)
    if teacher_weights is None:
        t_avg = jnp.mean(t, axis=0)
    else:
        t_avg = jnp.tensordot(teacher_weights.astype(jnp.float32), t,
                              axes=([0], [0]))
    return avg_logits_kl_pre(student_logits, t_avg, temperature)


def normalize_teacher_weights(weights) -> Optional[jnp.ndarray]:
    """Importance weights -> normalized [K] jnp.float32 (None passthrough)."""
    if weights is None:
        return None
    w = np.asarray(weights, np.float64)
    s = w.sum()
    if s <= 0:
        raise ValueError(f"teacher weights must have a positive sum, got {w}")
    return jnp.asarray(w / s, jnp.float32)


@dataclasses.dataclass
class FusionConfig:
    """Paper defaults (§4.1): Adam 1e-3 + cosine, 1e4 step cap, 1e3 patience.

    ``optimizer``/``swag_samples`` reproduce the Table 7 ablation: server
    distillation with SGD, Adam (default), or Adam + SWAG-sampled extra
    teachers (the FedDistill [10] variant; see ``core/swag.py``).

    ``logit_bank``: ``auto`` precomputes the teacher-logit bank whenever
    the source exposes an indexable pool, ``on`` insists (warns + falls
    back if it cannot), ``off`` keeps per-step teacher forwards.
    ``bank_dtype`` trades bank memory against trajectory fidelity:
    ``float32`` is bitwise-identical to on-the-fly, ``bfloat16`` halves
    the rows, ``int8`` / ``fp8_e4m3`` store ~4x-smaller quantized rows
    plus one fp32 scale per row, dequantized inside the fused kernel
    (docs/distill_fast_path.md).

    ``batch_sizes`` (heterogeneous fusion only) gives each prototype
    group its own distillation batch size; ``distill_bucket`` buckets
    those sizes into run-fixed padded capacities exactly like the client
    axis (``core/client.py:bucket_capacities`` — ``none`` pads every
    group to the largest size, ``pow2``/``quantile`` give small students
    intermediate capacities so they stop padding to the largest
    student's batch shape).  Padded rows are sliced off before the loss,
    so trajectories are identical across kinds."""

    max_steps: int = 10_000
    patience: int = 1_000
    eval_every: int = 100
    batch_size: int = 128
    lr: float = 1e-3
    temperature: float = 1.0
    use_fused_kernel: Union[bool, str] = "auto"  # True | False | "auto"
    optimizer: str = "adam"  # adam | sgd   (Table 7)
    swag_samples: int = 0    # extra SWAG teachers (Table 7 "SWAG" row)
    swag_scale: float = 0.5
    logit_bank: str = "auto"       # auto | on | off
    bank_dtype: str = "float32"    # float32 | bfloat16 | int8 | fp8_e4m3
    # per-group distill batch sizes (heterogeneous fusion; None = uniform
    # batch_size) and their bucketing into padded capacities
    batch_sizes: Optional[Tuple[int, ...]] = None
    distill_bucket: str = "none"   # none | pow2 | quantile
    distill_max_buckets: int = 4
    # internal: the run-fixed padded capacity this distill's batches are
    # padded to (set per group by heterogeneous fusion, not by users)
    batch_capacity: Optional[int] = None
    # divergence guard (docs/robustness.md): check the student params for
    # non-finite values after every compiled chunk and roll back to the
    # last-good params instead of distilling on.  Off by default — the
    # per-chunk finiteness check costs a device reduction, and fault-free
    # configs must stay bit-identical in behavior AND step count.
    divergence_guard: bool = False


def make_teacher_logits_fn(net: Net, teacher_stack):
    """Stacked homogeneous teachers -> fn(x) -> [K, B, C].

    The stamped ``net``/``stack`` attributes let the distill loop pass the
    stack as an ARGUMENT to one cross-round cached compiled chunk instead
    of baking it into a fresh closure (and recompiling) every round."""

    def fn(x):
        return jax.vmap(lambda p: net.apply(p, x, train=False))(teacher_stack)

    fn.n_teachers = tree_leading_dim(teacher_stack)
    fn.net = net
    fn.stack = teacher_stack
    return fn


def expected_distill_steps(fusion: FusionConfig, have_val: bool) -> int:
    """A-priori estimate of how many distillation steps a fusion will run
    — the logit bank's ``auto`` break-even input (docs/distill_fast_path.md).

    Without validation (no early stopping) the loop runs ``max_steps``
    exactly.  With validation, the EARLIEST possible plateau stop is one
    patience window past the first eval (the first eval always improves on
    the ``-1.0`` initial best), rounded up to the ``eval_every`` chunk
    grid; a small ``patience`` therefore bounds the whole run well below
    ``max_steps`` and the bank build may no longer amortize."""
    if not have_val:
        return fusion.max_steps
    ee = max(1, int(fusion.eval_every))
    earliest_stop = ee * -(-(ee + int(fusion.patience)) // ee)
    return min(int(fusion.max_steps), earliest_stop)


# info["bank_decision"] / RoundLog.bank values per resolve_bank reason
_BANK_DECISIONS = {"built": "bank", "reused": "bank_reused",
                   "skipped_small_run": "skipped_small_run"}


def _bank_decision(reason: str) -> str:
    return _BANK_DECISIONS.get(reason, "on_the_fly")


def _resolve_fused(flag):
    """use_fused_kernel -> bool without importing Pallas when it's off."""
    if flag is False or flag is None:
        return False
    from repro.kernels.ops import use_pallas
    return use_pallas(flag)


def _count_teachers(teacher_logit_fns, source, batch_size) -> int:
    """Total K across groups, for the forward-call accounting.  Stamped
    fns (:func:`make_teacher_logits_fn`) count their stacks' leading
    axes, as the bank builder does; plain callables are counted by shape
    evaluation, falling back to an ``n_teachers`` attribute when the
    source or a fn cannot be abstractly traced."""
    if not teacher_logit_fns:
        return 0
    k_total = stacked_teacher_count(teacher_logit_fns)
    if k_total is not None:
        return k_total
    try:
        x = jax.eval_shape(lambda k: source.sample(k, batch_size),
                           jax.ShapeDtypeStruct((2,), jnp.uint32))
        return sum(int(jax.eval_shape(f, x).shape[0])
                   for f in teacher_logit_fns)
    except Exception:  # counting is informational — never fail the fusion
        return sum(int(getattr(f, "n_teachers", 1))
                   for f in teacher_logit_fns)


# Counts TRACES of the compiled distill chunk: the counter bumps via a
# python side effect inside the traced body, so it only moves when jax
# actually re-traces/compiles — the tests' evidence that fusion no longer
# recompiles every round.  Same process-wide counter type as
# TEACHER_FORWARDS (imported above); registered in the unified metrics
# registry under a dotted name, aliased here for the historic interface.
CHUNK_COMPILES = REGISTRY.counter("core.feddf.chunk_compiles")

# Cross-round compiled-program caches, weakly keyed by the student Net
# (id()-keyed dicts could hand back a stale program once ids are reused
# after GC — see core/client.py's eval caches for the idiom).  Values
# close over the teacher nets / source / plain teacher callables, pinning
# them alive, so the id()s inside the inner keys stay valid for exactly
# as long as their entries exist.
_CHUNK_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_VAL_EVAL_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _fusion_chunk_key(fusion: FusionConfig, fused: bool,
                      weighted: bool = False) -> tuple:
    return (fusion.optimizer, float(fusion.lr), int(fusion.max_steps),
            int(fusion.eval_every), int(fusion.batch_size),
            int(fusion.batch_capacity or fusion.batch_size),
            float(fusion.temperature), bool(fused), bool(weighted))


def _make_distill_opt(fusion: FusionConfig):
    if fusion.optimizer == "sgd":  # Table 7: same cosine schedule, SGD rule
        from repro.optim.optimizers import sgd as _sgd
        return _sgd(cosine(fusion.lr, fusion.max_steps))
    return adam(cosine(fusion.lr, fusion.max_steps))


def _kernel_mesh(*trees):
    """The multi-device mesh a fused-kernel distillation runs on: the
    first one among its inputs' shardings (a client-sharded round's
    teacher stack), or None when they sit on one device."""
    for leaf in jax.tree.leaves(trees):
        sharding = getattr(leaf, "sharding", None)
        if isinstance(sharding, NamedSharding) and sharding.mesh.size > 1:
            return sharding.mesh
    return None


def _build_chunk(student_net: Net, source, fusion: FusionConfig,
                 fused: bool, donate: bool, *, mode: str,
                 teacher_nets: Tuple[Net, ...] = (),
                 teacher_fns: Sequence[Callable] = (),
                 weighted: bool = False, mesh=None):
    """One jit'd ``eval_every``-step distillation chunk.

    ``mode`` selects what crosses the call boundary as ARGUMENTS (so the
    compiled program is reusable across rounds):

      bank     extra = (pool, bank_logits, scales) — gather rows by
               sampled index; ``scales`` are the per-row fp32 dequant
               scales of a quantized bank (None otherwise)
      stacked  extra = one [K_g, ...] teacher pytree per teacher net
      plain    extra = () — legacy closure over arbitrary callables

    ``weighted`` (stacked/plain only; a bank pre-weights its rows at
    build) appends one normalized [K] teacher-weight vector to ``extra``
    and replaces the uniform teacher-logit mean with the weighted
    consensus — the staleness-importance path (docs/population.md).

    ``fusion.batch_capacity`` (distill-axis bucketing) pads the sampled
    batch from ``batch_size`` up to the group's run-fixed capacity so G
    heterogeneous students share compiled shapes; the padded rows are
    sliced off before the loss, so the update is identical to the
    unpadded one.

    ``mesh`` (fused kernels on multi-device inputs): Mosaic kernels cannot
    be partitioned automatically, so the chunk runs under ``shard_map``
    with every operand replicated — each device distils the whole batch,
    as the automatically partitioned jnp path does.
    """
    opt = _make_distill_opt(fusion)
    if fused:
        from repro.kernels.ops import (ensemble_kl_loss,
                                       ensemble_kl_loss_bank,
                                       ensemble_kl_loss_pre)
    bsz = int(fusion.batch_size)
    cap = int(fusion.batch_capacity or bsz)
    if cap < bsz:
        raise ValueError(f"batch_capacity {cap} < batch_size {bsz}")

    def chunk(params, opt_state, key, step0, *extra):
        CHUNK_COMPILES.add(1)  # trace-time side effect: counts compiles
        if weighted and mode != "bank":
            t_extra, tw = extra[:-1], extra[-1]
        else:
            t_extra, tw = extra, None
        mask = student_net.trainable_mask(params)

        def body(carry, _):
            params, opt_state, key, step = carry
            key, k1 = jax.random.split(key)
            if mode == "bank":
                # fast path: gather pool rows + precomputed averaged
                # teacher logits by the SAME indices sample() would draw
                pool, bank_logits, scales = extra
                idx = source.sample_indices(k1, bsz)
                idx_x = (jnp.concatenate(
                    [idx, jnp.zeros((cap - bsz,), idx.dtype)])
                    if cap > bsz else idx)
                x = pool[idx_x]
                if not fused:
                    t_avg = dequantize_rows(
                        bank_logits[idx],
                        None if scales is None else scales[idx])
            else:
                x = source.sample(k1, bsz)
                if cap > bsz:
                    x = jnp.concatenate(
                        [x, jnp.zeros((cap - bsz,) + x.shape[1:], x.dtype)])
                if mode == "stacked":
                    t_logits = jnp.concatenate(
                        [jax.vmap(lambda p: net.apply(p, x, train=False)
                                  )(stack)
                         for net, stack in zip(teacher_nets, t_extra)],
                        axis=0)
                else:
                    t_logits = jnp.concatenate(
                        [jnp.asarray(f(x)) for f in teacher_fns], axis=0)
                if cap > bsz:
                    t_logits = t_logits[:, :bsz]

            def loss_fn(p):
                s_logits = student_net.apply(p, x, train=True)
                if cap > bsz:
                    s_logits = s_logits[:bsz]
                if mode == "bank":
                    if fused:
                        # gather + dequantize + KL fused in one kernel:
                        # neither the gathered nor the dequantized [B, C]
                        # teacher rows materialize in HBM
                        return ensemble_kl_loss_bank(
                            s_logits, bank_logits, scales, idx,
                            temperature=fusion.temperature)
                    return avg_logits_kl_pre(s_logits, t_avg,
                                             fusion.temperature)
                if fused:
                    if tw is None:
                        return ensemble_kl_loss(
                            s_logits, t_logits,
                            temperature=fusion.temperature)
                    t_consensus = jnp.tensordot(
                        tw.astype(jnp.float32),
                        t_logits.astype(jnp.float32), axes=([0], [0]))
                    return ensemble_kl_loss_pre(
                        s_logits, t_consensus,
                        temperature=fusion.temperature)
                return avg_logits_kl(s_logits, t_logits, fusion.temperature,
                                     teacher_weights=tw)

            grads = jax.grad(loss_fn)(params)
            grads = jax.tree.map(lambda g, m: g if m else jnp.zeros_like(g),
                                 grads, mask)
            deltas, opt_state2 = opt.update(grads, opt_state, params, step)
            params = apply_updates(params, deltas)
            return (params, opt_state2, key, step + 1), None

        (params, opt_state, key, step), _ = jax.lax.scan(
            body, (params, opt_state, key, step0), None,
            length=fusion.eval_every)
        return params, opt_state, key, step

    if mesh is not None:
        chunk = jax.shard_map(chunk, mesh=mesh, in_specs=P(),
                              out_specs=P(), check_vma=False)
    return jax.jit(chunk, donate_argnums=(0, 1) if donate else ())


def _get_chunk(student_net: Net, teacher_logit_fns: Sequence[Callable],
               source, fusion: FusionConfig, fused: bool,
               bank: Optional[LogitBank], donate: bool,
               teacher_weights=None, mesh=None):
    """The cross-round cached chunk for this (student, teachers, source,
    fusion) configuration plus its per-call extra arguments.  Cached so
    round t+1's fusion reuses round t's compiled program instead of
    re-jitting a fresh closure (the ROADMAP-flagged residual overhead);
    jax's own signature cache handles shape changes (e.g. rng-driven
    heterogeneous cohort sizes).

    ``teacher_weights`` (normalized [K] over all teachers; None =
    uniform) selects the weighted-consensus chunk variant — the weights
    cross the jit boundary as an argument, so weighted rounds share one
    compiled program too.  Bank mode ignores it: a weighted bank already
    folded the weights into its rows at build time."""
    if bank is not None:
        mode = "bank"
    elif all(hasattr(f, "net") and hasattr(f, "stack")
             for f in teacher_logit_fns):
        mode = "stacked"
    else:
        mode = "plain"
    weighted = teacher_weights is not None and mode != "bank"
    w_extra = (jnp.asarray(teacher_weights, jnp.float32),) if weighted \
        else ()
    if mode == "plain":
        # arbitrary callables are usually built fresh per call — caching
        # by their ids would grow one pinned compiled program per round
        # with zero hits, so keep the historic per-call jit for them
        return _build_chunk(student_net, source, fusion, fused, donate,
                            mode="plain", weighted=weighted,
                            teacher_fns=tuple(teacher_logit_fns),
                            mesh=mesh), w_extra
    teacher_nets = (tuple(f.net for f in teacher_logit_fns)
                    if mode == "stacked" else ())
    per = _CHUNK_CACHE.get(student_net)
    if per is None:
        per = {}
        _CHUNK_CACHE[student_net] = per
    key = (_fusion_chunk_key(fusion, fused, weighted), mode, id(source),
           tuple(id(n) for n in teacher_nets), bool(donate), mesh)
    fn = per.get(key)
    if fn is None:
        fn = _build_chunk(student_net, source, fusion, fused, donate,
                          mode=mode, teacher_nets=teacher_nets,
                          weighted=weighted, mesh=mesh)
        per[key] = fn
    if mode == "bank":
        # scales is None for fp32/bf16 banks — jit treats it as an empty
        # pytree arg, so one cached chunk covers both layouts per shape
        extra = (bank.pool, bank.logits, bank.scales)
    else:
        extra = tuple(f.stack for f in teacher_logit_fns) + w_extra
    return fn, extra


def _get_val_eval(student_net: Net, val_x, val_y):
    """Cached jitted eval_update for this (net, val set) — the
    between-chunk validation pass used to re-jit per distill() call."""
    per = _VAL_EVAL_CACHE.get(student_net)
    if per is None:
        per = {}
        _VAL_EVAL_CACHE[student_net] = per
    key = (id(val_x), id(val_y))
    entry = per.get(key)
    if entry is None:
        acc_fn = _make_acc_fn(student_net, val_x, val_y)

        @jax.jit
        def eval_update(params, step, best):
            best_params, best_acc, best_step = best
            acc = acc_fn(params)
            best = jax.lax.cond(
                acc > best_acc,
                lambda: (params, acc, step),
                lambda: (best_params, best_acc, best_step))
            return acc, best

        # pin the CALLER's arrays: acc_fn closes over device copies, so
        # without these refs the originals could be GC'd and their ids
        # reused by different data
        entry = (eval_update, (val_x, val_y))
        per[key] = entry
    return entry[0]


def _make_acc_fn(net: Net, x, y, batch_size: int = 512):
    """Jitted top-1 accuracy over fixed padded batches — the distill
    loop's validation eval stays on device (only the scalar crosses)."""
    x = jnp.asarray(x)
    y = jnp.asarray(y)
    n = int(x.shape[0])
    bs = min(batch_size, n)
    nb = -(-n // bs)
    pad = nb * bs - n
    if pad:
        x = jnp.concatenate([x, jnp.zeros((pad,) + x.shape[1:], x.dtype)])
        y = jnp.concatenate([y, jnp.zeros((pad,), y.dtype)])
    valid = (jnp.arange(nb * bs) < n).reshape(nb, bs)
    xs = x.reshape((nb, bs) + x.shape[1:])
    ys = y.reshape(nb, bs)

    @jax.jit
    def acc(params):
        def body(c, inp):
            xb, yb, mb = inp
            pred = jnp.argmax(net.apply(params, xb, train=False), axis=-1)
            return c + jnp.sum(jnp.where(mb, pred == yb, False)), None

        c, _ = jax.lax.scan(body, jnp.int32(0), (xs, ys, valid))
        return c.astype(jnp.float32) / n

    return acc


def distill(
    student_net: Net,
    student_params,
    teacher_logit_fns: Sequence[Callable],
    source: DistillSource,
    fusion: FusionConfig,
    val_x: Optional[np.ndarray] = None,
    val_y: Optional[np.ndarray] = None,
    seed: int = 0,
    bank: Optional[LogitBank] = None,
    teacher_weights=None,
) -> Tuple[dict, dict]:
    """Run server-side ensemble distillation; returns (params, info).

    ``teacher_logit_fns``: callables x -> [K_g, B, C]; logits are averaged
    over *all* teachers across groups (Algorithm 3 line 14).  Pass a
    prebuilt ``bank`` to share one teacher-logit bank across students
    (heterogeneous fusion); with ``bank=None`` and ``fusion.logit_bank``
    != 'off' the bank is built here when the source has a pool.

    ``teacher_weights`` ([sum K_g] over all teachers in concat order, any
    positive scale; None = uniform) replaces the AVGLOGITS uniform mean
    with a weighted teacher consensus — the buffered-async driver's
    FedAsync staleness importance (docs/population.md).  It folds into
    the bank rows at build time, or crosses the jit boundary as a chunk
    argument on the on-the-fly path; None keeps every historic trajectory
    bitwise-identical.
    """
    opt = _make_distill_opt(fusion)

    fused = _resolve_fused(fusion.use_fused_kernel)
    teacher_weights = normalize_teacher_weights(teacher_weights)

    built_here = False
    decision = "bank" if bank is not None else "on_the_fly"
    if bank is None and fusion.logit_bank != "off" and teacher_logit_fns:
        bank, reason = resolve_bank(
            teacher_logit_fns, source, fusion,
            expected_steps=expected_distill_steps(fusion,
                                                  val_x is not None),
            teacher_weights=teacher_weights)
        decision = _bank_decision(reason)
        built_here = bank is not None and not bank.reused
    n_teachers = _count_teachers(teacher_logit_fns, source,
                                 fusion.batch_size)

    donate = donation_supported()
    mesh = (_kernel_mesh(student_params, bank.logits if bank is not None
                         else [getattr(f, "stack", None)
                               for f in teacher_logit_fns])
            if fused else None)
    # the compiled chunk is cached ACROSS rounds (teacher stacks / bank
    # rows cross the call boundary as arguments): round t+1 reuses round
    # t's program instead of re-jitting a fresh closure per call
    chunk, extra = _get_chunk(student_net, teacher_logit_fns, source,
                              fusion, fused, bank, donate,
                              teacher_weights=teacher_weights, mesh=mesh)

    # the first chunk call donates its params buffer: never donate the
    # caller's — copy once, reuse for 10k steps
    params = (jax.tree.map(jnp.copy, student_params) if donate
              else student_params)
    opt_state = opt.init(params)

    have_val = val_x is not None
    if have_val:
        eval_update = _get_val_eval(student_net, val_x, val_y)
        best = (student_params, jnp.float32(-1.0), jnp.int32(0))

    key = jax.random.PRNGKey(seed)
    step = jnp.int32(0)
    history = []
    guard = bool(getattr(fusion, "divergence_guard", False))
    diverged = False
    # the chunks and validation checks alone: the bank's build (or reuse)
    # and the chunk's lookup above are spans or work of their own
    with _trace.span("distill") as sp:
        while int(step) < fusion.max_steps:
            params, opt_state, key, step = chunk(params, opt_state, key,
                                                 step, *extra)
            if bank is None and n_teachers:
                TEACHER_FORWARDS.add(fusion.eval_every * n_teachers)
            if guard and not bool(tree_isfinite(params)):
                # divergence guard: a non-finite distill state can only
                # get worse — stop and roll back to the last-good params
                # (the best-val snapshot, or the pre-distill student)
                diverged = True
                break
            if have_val:
                acc, best = eval_update(params, step, best)
                history.append((int(step), float(acc)))
                if int(step) - int(best[2]) >= fusion.patience:
                    break  # early stopping: validation plateau (§4.1)
        n_steps = int(step)
        sp.annotate(steps=n_steps)

    if have_val:
        best_params, best_acc, best_step = (best[0], float(best[1]),
                                            int(best[2]))
    else:
        best_params = student_params if diverged else params
        best_acc, best_step = -1.0, 0
    fwd_count = (bank.n_teacher_batch_forwards if built_here
                 else (0 if bank is not None else n_steps * n_teachers))
    cap = int(fusion.batch_capacity or fusion.batch_size)
    info = {"steps": n_steps, "best_val_acc": best_acc,
            "best_step": best_step, "val_history": history,
            "diverged": diverged,
            "logit_bank": bank is not None,
            "bank_decision": decision,
            "bank_dtype": bank.dtype_name if bank is not None else "",
            "bank_nbytes": bank.nbytes if bank is not None else 0,
            "bank_build_s": bank.build_time_s if built_here else 0.0,
            "teacher_batch_forwards": fwd_count,
            # distill-axis bucketing accounting: rows computed but sliced
            # off before the loss, per step (0 = unbucketed/exact-fit)
            "batch_capacity": cap,
            "padded_rows_per_step": cap - int(fusion.batch_size)}
    return best_params, info


def filter_teacher_stack(net: Net, stack, probe_x,
                         sigma: float = 6.0) -> Tuple[np.ndarray, int]:
    """Teacher-consensus filter (docs/robustness.md): which teachers of a
    stacked [K, ...] ensemble may vote?

    Each teacher's logits on one probe batch are compared against the
    element-wise median over finite teachers; a teacher is dropped when
    its logits are non-finite anywhere, or when its mean absolute
    deviation from the median robust-z-scores beyond ``sigma`` among its
    peers.  Runs BEFORE the logit-bank rows are built, so a poisoned
    teacher never contaminates the distillation targets.

    Returns ``(kept_indices, n_dropped)``; ``kept_indices`` may be empty
    when every teacher is non-finite (callers should then skip fusion).
    """
    logits = np.asarray(
        jax.vmap(lambda p: net.apply(p, probe_x, train=False))(stack),
        np.float64)                                   # [K, B, C]
    k = logits.shape[0]
    finite = np.isfinite(logits).all(axis=(1, 2))
    if not finite.any():
        return np.empty(0, np.int64), k
    med = np.median(logits[finite], axis=0)           # [B, C]
    dist = np.full(k, np.inf)
    dist[finite] = np.mean(np.abs(logits[finite] - med), axis=(1, 2))
    fd = dist[finite]
    center = float(np.median(fd))
    mad = float(np.median(np.abs(fd - center)))
    # same robust-z floor as the upload screen: a collapsed MAD must not
    # flag honest teachers over sub-percent logit jitter
    denom = 1.4826 * mad + 0.05 * abs(center) + 1e-12
    ok = finite & (np.abs(dist - center) / denom <= sigma)
    if not ok.any():  # degenerate: keep the single most central teacher
        ok[int(np.argmin(dist))] = True
    kept = np.flatnonzero(ok)
    return kept.astype(np.int64), int(k - kept.size)


def feddf_fuse_stacked(
    net: Net,
    teacher_stack,
    weights: Sequence[float],
    source: DistillSource,
    fusion: FusionConfig,
    val_x=None,
    val_y=None,
    seed: int = 0,
    student: Optional[dict] = None,
    teacher_weights=None,
) -> Tuple[dict, dict]:
    """Algorithm 1 on an ALREADY-STACKED [K, ...] teacher pytree — the round
    engine hands its batched-training output straight in, no per-round
    ``tree_stack`` re-copy.  ``student=None`` initialises from the weighted
    average (line 6).  ``teacher_weights`` (per-teacher importance, e.g.
    the buffered-async ``(1+s)^-a`` staleness weights) biases the teacher
    consensus; None keeps the paper's uniform AVGLOGITS bitwise."""
    if student is None:
        student = tree_weighted_mean_stacked(teacher_stack, weights)
    if fusion.swag_samples > 0:  # Table 7: FedDistill/SWAG teacher pool
        from repro.core.swag import swag_teachers_stacked
        teacher_stack = swag_teachers_stacked(
            teacher_stack, fusion.swag_samples, scale=fusion.swag_scale,
            seed=seed)
        if teacher_weights is not None:
            # SWAG samples are drawn from the whole ensemble's posterior:
            # give each appended sample the ensemble-average importance
            tw = np.asarray(teacher_weights, np.float64)
            teacher_weights = np.concatenate(
                [tw, np.full(fusion.swag_samples, tw.mean())])
    tfn = make_teacher_logits_fn(net, teacher_stack)
    return distill(net, student, [tfn], source, fusion, val_x, val_y, seed,
                   teacher_weights=teacher_weights)


def feddf_fuse_homogeneous(
    net: Net,
    client_params: List[dict],
    client_weights: Sequence[float],
    source: DistillSource,
    fusion: FusionConfig,
    val_x=None,
    val_y=None,
    seed: int = 0,
    init_from: str = "average",
    prev_global: Optional[dict] = None,
) -> Tuple[dict, dict]:
    """List-of-pytrees wrapper over :func:`feddf_fuse_stacked`.
    ``init_from='previous'`` reproduces the Table 5 ablation (initialise
    from last round's fused model instead of the weighted average)."""
    student = (None if init_from == "average" or prev_global is None
               else prev_global)
    return feddf_fuse_stacked(net, tree_stack(client_params), client_weights,
                              source, fusion, val_x, val_y, seed,
                              student=student)


def feddf_fuse_heterogeneous_stacked(
    prototypes: List[Tuple[Net, Optional[dict], Sequence[float]]],
    source: DistillSource,
    fusion: FusionConfig,
    val_x=None,
    val_y=None,
    seed: int = 0,
    importances: Optional[List[Optional[np.ndarray]]] = None,
) -> Tuple[List[Optional[dict]], List[dict]]:
    """Algorithm 3 on stacked per-group teacher pytrees: every group's
    student distills against the ALL-groups teacher ensemble.

    ``importances`` (one optional [K_g] array per group, aligned with
    ``prototypes``) weights each teacher's vote in the shared consensus
    — groups without importance contribute uniformly.  All-None keeps
    the historic uniform path bitwise.

    ``prototypes``: per group (net, stacked params [K_g, ...] or None,
    data weights).  Returns (fused params per group, info per group).
    The teacher-logit bank is built ONCE here and shared by every group's
    student — the G× redundant re-forwarding of the same all-groups
    ensemble collapses into a single pass over the pool.

    ``fusion.batch_sizes`` gives each group its own distillation batch
    size; the sizes are bucketed into run-fixed padded capacities
    (``fusion.distill_bucket``: ``none`` pads every group to the largest
    size, ``pow2``/``quantile`` give small students intermediate
    capacities; docs/bucketing.md).
    Trajectories are identical across kinds — padded rows never reach
    the loss.
    """
    bsizes = getattr(fusion, "batch_sizes", None)
    caps_of = None
    if bsizes is not None:
        if len(bsizes) != len(prototypes):
            raise ValueError(
                f"fusion.batch_sizes has {len(bsizes)} entries for "
                f"{len(prototypes)} prototype groups")
        from repro.core.client import assign_buckets, bucket_capacities
        bsizes = [int(b) for b in bsizes]
        caps = bucket_capacities(bsizes, fusion.distill_bucket,
                                 fusion.distill_max_buckets)
        which = assign_buckets(bsizes, caps)
        caps_of = [int(caps[w]) for w in which]
    teacher_fns = [make_teacher_logits_fn(net, stack)
                   for net, stack, _ in prototypes if stack is not None]
    # per-teacher importance in teacher_fns' concat order (groups without
    # importance vote uniformly); all-None stays on the uniform path
    teacher_weights = None
    if importances is not None and any(i is not None for i in importances):
        pieces = []
        for (net_, stack, _), imp in zip(prototypes, importances):
            if stack is None:
                continue
            k_g = tree_leading_dim(stack)
            pieces.append(np.ones(k_g, np.float64) if imp is None
                          else np.asarray(imp, np.float64))
        teacher_weights = normalize_teacher_weights(np.concatenate(pieces))
    # the bank is shared by every group-student, so the break-even input
    # is the G-fold TOTAL expected rows, not one student's
    n_students = len(teacher_fns)
    bank, reason = resolve_bank(
        teacher_fns, source, fusion,
        expected_steps=(expected_distill_steps(fusion, val_x is not None)
                        * max(1, n_students)),
        teacher_weights=teacher_weights)
    decision = _bank_decision(reason)
    if bank is None and fusion.logit_bank != "off":
        # resolution already happened (and warned, for 'on') here at the
        # fuse level — stop each group's distill from re-trying it
        fusion = dataclasses.replace(fusion, logit_bank="off")

    fused, infos = [], []
    build_attributed = bank is not None and bank.reused  # reuse: no build
    for gi, (net, stack, weights) in enumerate(prototypes):
        if stack is None:
            fused.append(None)
            infos.append({"skipped": True})
            continue
        student = tree_weighted_mean_stacked(stack, weights)  # Alg.3 line 11
        fusion_g = fusion
        if caps_of is not None:
            fusion_g = dataclasses.replace(
                fusion, batch_size=bsizes[gi], batch_capacity=caps_of[gi],
                batch_sizes=None)
        p, info = distill(net, student, teacher_fns, source, fusion_g,
                          val_x, val_y, seed + gi, bank=bank,
                          teacher_weights=teacher_weights)
        info["bank_decision"] = decision
        if bank is not None and not build_attributed:
            # charge the one-time build to the first fused group so the
            # round's total teacher-forward cost shows up in the logs
            info = dict(info, bank_build_s=bank.build_time_s,
                        teacher_batch_forwards=bank.n_teacher_batch_forwards)
            build_attributed = True
        fused.append(p)
        infos.append(info)
    return fused, infos


def feddf_fuse_heterogeneous(
    prototypes: List[Tuple[Net, List[dict], Sequence[float]]],
    source: DistillSource,
    fusion: FusionConfig,
    val_x=None,
    val_y=None,
    seed: int = 0,
) -> Tuple[List[Optional[dict]], List[dict]]:
    """List-of-pytrees wrapper over
    :func:`feddf_fuse_heterogeneous_stacked`."""
    stacked = [(net, tree_stack(plist) if plist else None, weights)
               for net, plist, weights in prototypes]
    return feddf_fuse_heterogeneous_stacked(stacked, source, fusion,
                                            val_x, val_y, seed)
