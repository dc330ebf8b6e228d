"""Client-side local training (Algorithm 2).

The batches for every epoch are materialised as arrays [n_steps, B, ...]
outside and looped over inside one jit-compiled program — orders of
magnitude faster than a python loop per step, and the compiled function
is reused across clients and rounds (same shapes).

Two entry points:

* :func:`make_local_update` — one client per call (the original path, kept
  for tests/benchmarks and as the numerical reference).
* :func:`make_batched_local_update` — ALL active clients of a round in one
  compiled program: batch tensors are stacked to [K, n_steps, B, ...] and
  the clients train one after another, each for exactly its own step
  count (see docs/round_engine.md).  FedProx anchoring, quantized
  forwards, and DP privatization of the uploads all run inside the jitted
  path; an optional mesh shards the leading client axis across devices
  (``shard_map``) so each device trains its share of the clients.

Supports: plain SGD (FedAvg), proximal term (FedProx, Appendix B), arbitrary
optimizers (the paper's Adam-local-training ablation, Table 6), BatchNorm
running-stats maintenance, and a quantize transform for low-bit clients
(Table 4, straight-through estimator).
"""
from __future__ import annotations

import weakref
from typing import Callable, List, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import REGISTRY
from repro.common.pytree import tree_sq_dist
from repro.core.nets import Net
from repro.optim.optimizers import Optimizer, apply_updates

# Counts TRACES of the batched client update (the python side effect only
# fires when jax re-traces, i.e. compiles a new program) — the tests'
# evidence that the compile count stays at one per prototype per run
# instead of growing with rng-driven cohort shapes.  Registered in the
# unified metrics registry; this module-level alias keeps the historic
# reset()/.count interface for tests.
CLIENT_COMPILES = REGISTRY.counter("core.client.compiles")
# Client-steps the cohorts hold (``RoundBatches.real_steps``); the
# engine's ``train_clients`` adds each round's.
REAL_STEPS = REGISTRY.counter("core.client.real_steps")


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def _local_step(net: Net, opt: Optimizer, prox_mu: float,
                quantize: Optional[Callable]):
    """One local step: ``step(params, state, i, x, y, anchor, mask) ->
    (params, state)``, shared by both entry points."""

    def loss_fn(params, x, y, anchor):
        p = quantize(params) if quantize is not None else params
        logits, stats = net.apply_with_stats(p, x)
        loss = softmax_xent(logits, y)
        if prox_mu > 0.0:
            loss = loss + 0.5 * prox_mu * tree_sq_dist(params, anchor)
        return loss, stats

    def step(params, state, i, x, y, anchor, mask):
        grads, stats = jax.grad(loss_fn, has_aux=True)(params, x, y, anchor)
        grads = jax.tree.map(lambda g, m: g if m else jnp.zeros_like(g),
                             grads, mask)
        deltas, state = opt.update(grads, state, params, i)
        new_params = apply_updates(params, deltas)
        # take BN running stats from the forward pass (non-trainable)
        new_params = jax.tree.map(
            lambda new, st, m: new if m else st.astype(new.dtype),
            new_params, stats, mask)
        return new_params, state

    return step


def make_local_update(net: Net, opt: Optimizer, *, prox_mu: float = 0.0,
                      quantize: Optional[Callable] = None):
    """Returns jit'd fn(params, xb [n,B,...], yb [n,B], anchor) -> params.

    ``anchor`` is the round's global model (FedProx pulls towards it; pass
    the initial params when prox_mu == 0, it is ignored).
    """
    step = _local_step(net, opt, prox_mu, quantize)

    @jax.jit
    def run(params, xb, yb, anchor):
        mask = net.trainable_mask(params)

        def body(carry, batch):
            params, state, i = carry
            params, state = step(params, state, i, *batch, anchor, mask)
            return (params, state, i + 1), None

        (params, _, _), _ = jax.lax.scan(
            body, (params, opt.init(params), jnp.int32(0)), (xb, yb))
        return params

    return run


def trip_counts(step_mask):
    """Steps each client trains: the true entries of its prefix step mask
    ([..., n] -> [...]).  :func:`make_batched_local_update` loops exactly
    this many times per client."""
    return step_mask.sum(axis=-1, dtype=np.int32)


def make_batched_local_update(net: Net, opt: Optimizer, *,
                              prox_mu: float = 0.0,
                              quantize: Optional[Callable] = None,
                              dp_clip: Optional[float] = None,
                              dp_noise_multiplier: float = 0.0,
                              mesh=None, client_axis: str = "data",
                              donate_batches: bool = False):
    """Local training for all K active clients of a round, one program.

    Returns jit'd ``fn(params, xb [K,n,B,...], yb [K,n,B], anchor,
    step_mask [K,n], dp_keys [K,2]) -> stacked params [K, ...]``.

    The clients train one after another (``lax.map`` over the client
    axis), each in a ``while_loop`` that runs exactly its own step count,
    :func:`trip_counts` of its prefix ``step_mask`` (the layout
    :func:`build_batched_batches` builds).  The padded tail of a short
    client, and a padded client slot with an all-False mask, never run on
    the device, so each client's trajectory is the sequential
    :func:`make_local_update` run on its own (unpadded) batches, and only
    one client's training state is live at a time.  The shapes, and so
    the compiled program, stay fixed for any cohort's step counts.

    When ``dp_clip`` is set, every client's upload is clipped + noised
    (``core/privacy.py``) inside the same jitted program, keyed per client
    by ``dp_keys``.  With a ``mesh``, the leading client axis is sharded
    over ``client_axis`` via ``shard_map`` (K must divide the axis size):
    each device loops over its own clients.

    ``donate_batches=True`` donates the per-round scratch tensors
    (``xb``/``yb``/``step_mask``/``dp_keys``) so XLA reuses their (large)
    buffers instead of reallocating every round — the engine rebuilds
    them each round and never reads them back.  ``params``/``anchor`` are
    deliberately NOT donated: the engine passes the same globals buffer
    to every group and reads it again after training.  Callers that reuse
    their batch arrays across calls (benchmarks) must keep the default.
    """
    step = _local_step(net, opt, prox_mu, quantize)

    def one_client(params, anchor, xb, yb, step_mask, dp_key):
        mask = net.trainable_mask(params)
        n = trip_counts(step_mask)

        def body(carry):
            params, state, i = carry
            params, state = step(params, state, i, xb[i], yb[i], anchor,
                                 mask)
            return params, state, i + 1

        params, _, _ = jax.lax.while_loop(
            lambda carry: carry[2] < n, body,
            (params, opt.init(params), jnp.int32(0)))
        if dp_clip is not None:
            from repro.core.privacy import privatize_update
            params = privatize_update(anchor, params, clip=dp_clip,
                                      noise_multiplier=dp_noise_multiplier,
                                      key=dp_key)
        return params

    def clients(params, xb, yb, anchor, step_mask, dp_keys):
        return jax.lax.map(lambda c: one_client(params, anchor, *c),
                           (xb, yb, step_mask, dp_keys))

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        rep, cl = P(), P(client_axis)
        clients = jax.shard_map(clients, mesh=mesh,
                                in_specs=(rep, cl, cl, rep, cl, cl),
                                out_specs=cl, check_vma=False)

    def counted(params, xb, yb, anchor, step_mask, dp_keys):
        CLIENT_COMPILES.add(1)  # trace-time side effect: counts compiles
        return clients(params, xb, yb, anchor, step_mask, dp_keys)

    from repro.common.sharding import donation_supported
    donate = ((1, 2, 4, 5) if donate_batches and donation_supported()
              else ())
    return jax.jit(counted, donate_argnums=donate)


def build_batches(x: np.ndarray, y: np.ndarray, batch_size: int, epochs: int,
                  seed: int):
    """[n_steps, B, ...] arrays for the scanned local update."""
    rng = np.random.default_rng(seed)
    n = len(y)
    steps_per_epoch = max(1, n // batch_size)
    xs, ys = [], []
    for _ in range(epochs):
        if n >= batch_size:
            order = rng.permutation(n)[: steps_per_epoch * batch_size]
        else:
            order = rng.choice(n, size=batch_size, replace=True)
        xe = x[order].reshape(steps_per_epoch, batch_size, *x.shape[1:])
        ye = y[order].reshape(steps_per_epoch, batch_size)
        xs.append(xe)
        ys.append(ye)
    return np.concatenate(xs), np.concatenate(ys)


def n_local_steps(n_samples: int, batch_size: int, epochs: int) -> int:
    """Scan length :func:`build_batches` produces for a client of
    ``n_samples`` examples."""
    return epochs * max(1, n_samples // batch_size)


def build_batched_batches(x: np.ndarray, y: np.ndarray,
                          parts: Sequence[np.ndarray], batch_size: int,
                          epochs: int, seeds: Sequence[int],
                          n_steps: Optional[int] = None):
    """Stack every active client's scanned batches to one round tensor.

    Returns ``(xb [K,n,B,...], yb [K,n,B], step_mask [K,n])``.  Clients with
    fewer steps than ``n_steps`` (or the round maximum) are zero-padded at
    the END and masked out (a prefix mask: the batched update runs only
    the masked-in steps).  Pass a fixed ``n_steps`` (max over ALL clients)
    so every round reuses one compiled program.
    """
    per = [build_batches(x[idx], y[idx], batch_size, epochs, seed=s)
           for idx, s in zip(parts, seeds)]
    steps = [xb.shape[0] for xb, _ in per]
    n = max(steps) if n_steps is None else n_steps
    if n < max(steps):
        raise ValueError(f"n_steps={n} < max client steps {max(steps)}")
    k = len(per)
    xb = np.zeros((k, n) + per[0][0].shape[1:], per[0][0].dtype)
    yb = np.zeros((k, n) + per[0][1].shape[1:], per[0][1].dtype)
    step_mask = np.zeros((k, n), bool)
    for i, (xk, yk) in enumerate(per):
        xb[i, : len(xk)] = xk
        yb[i, : len(yk)] = yk
        step_mask[i, : len(xk)] = True
    return xb, yb, step_mask


# ---------------------------------------------------------------------------
# capacity bucketing (the distillation axis, core/feddf.py)
#
# Heterogeneous FedDF gives each prototype group its own distillation batch
# size; padding every group to the largest keeps ONE compiled chunk
# program.  Bucketing the sizes into a small FIXED set of capacities
# (computed once per run) pads each group only to its bucket's capacity,
# and the compile count stays bounded by the number of buckets.
# ---------------------------------------------------------------------------


def bucket_capacities(step_counts: Sequence[int], kind: str,
                      max_buckets: int = 4) -> List[int]:
    """The run-fixed set of padded capacities for a list of sizes.

    Returns an ascending list whose LAST entry is exactly
    ``max(step_counts)`` (so a single bucket reproduces the unbucketed
    path bit-for-bit) and whose length is ``<= max_buckets``.

    ``pow2``      capacities are powers of two clipped at the maximum; when
                  that yields more than ``max_buckets``, the LARGEST
                  capacities are kept (small sizes fall into bigger
                  buckets — more padding, never a truncated one).
    ``quantile``  capacities at ``max_buckets`` evenly-spaced quantiles of
                  the sizes' distribution (always including the max).
    ``none``      the single maximum: everything padded to it.
    """
    steps = sorted(int(s) for s in step_counts)
    if not steps:
        return [1]
    smax = steps[-1]
    if kind == "none" or max_buckets <= 1 or steps[0] == smax:
        return [smax]
    if kind == "pow2":
        caps = sorted({min(1 << (int(s) - 1).bit_length() if s > 1 else 1,
                           smax) for s in steps} | {smax})
        return caps[-max_buckets:]
    if kind == "quantile":
        qs = [steps[min(len(steps) - 1,
                        int(np.ceil((i + 1) / max_buckets * len(steps))) - 1)]
              for i in range(max_buckets)]
        return sorted(set(qs) | {smax})
    raise ValueError(f"unknown bucket kind {kind!r}; expected one of "
                     f"('none', 'pow2', 'quantile')")


def assign_buckets(step_counts: Sequence[int],
                   caps: Sequence[int]) -> np.ndarray:
    """Index of the smallest capacity holding each size."""
    idx = np.searchsorted(np.asarray(caps), np.asarray(step_counts),
                          side="left")
    if (idx >= len(caps)).any():
        raise ValueError(f"step count(s) exceed the largest bucket "
                         f"capacity {caps[-1]}")
    return idx


# jitted eval fns, cached per Net.  Weak keys: an id()-keyed dict could hand
# back a stale jitted fn for a different net once ids are reused after GC.
_EVAL_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()
_STACKED_EVAL_CACHE: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()


def _eval_fn(net: Net):
    fn = _EVAL_CACHE.get(net)
    if fn is None:
        # close over the apply fn, NOT the Net: a value that referenced its
        # weak key would pin the entry alive forever (no eviction)
        apply = net.apply
        fn = jax.jit(lambda pp, xx: jnp.argmax(apply(pp, xx, train=False),
                                               axis=-1))
        _EVAL_CACHE[net] = fn
    return fn


def stacked_logits_fn(net: Net):
    """Cached jitted fn(stacked params [K,...], x [B,...]) -> [K, B, C]."""
    fn = _STACKED_EVAL_CACHE.get(net)
    if fn is None:
        apply = net.apply  # see _eval_fn: never reference the weak key
        fn = jax.jit(jax.vmap(lambda p, xx: apply(p, xx, train=False),
                              in_axes=(0, None)))
        _STACKED_EVAL_CACHE[net] = fn
    return fn


def evaluate_stacked(net: Net, stack, x: np.ndarray, y: np.ndarray,
                     batch_size: int = 512) -> np.ndarray:
    """Per-client top-1 accuracies [K] from a stacked parameter pytree —
    one vmapped forward instead of K python-loop evaluations."""
    fn = stacked_logits_fn(net)
    k = jax.tree.leaves(stack)[0].shape[0]
    correct = np.zeros(k)
    for s in range(0, len(y), batch_size):
        logits = fn(stack, jnp.asarray(x[s : s + batch_size]))
        pred = np.asarray(jnp.argmax(logits, axis=-1))        # [K, b]
        correct += (pred == np.asarray(y[s : s + batch_size])[None]).sum(-1)
    return correct / len(y)


def evaluate(net: Net, params: dict, x: np.ndarray, y: np.ndarray,
             batch_size: int = 512, quantize: Optional[Callable] = None
             ) -> float:
    """Top-1 accuracy in eval mode (BN uses running stats)."""
    p = quantize(params) if quantize is not None else params
    apply = _eval_fn(net)
    correct = 0
    for s in range(0, len(y), batch_size):
        xb = jnp.asarray(x[s : s + batch_size])
        yb = y[s : s + batch_size]
        pred = np.asarray(apply(p, xb))
        correct += int((pred == yb).sum())
    return correct / len(y)
