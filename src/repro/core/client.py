"""Client-side local training (Algorithm 2).

One jit-compiled ``lax.scan`` runs all local steps of a round: the batches
for every epoch are materialised as arrays [n_steps, B, ...] outside and
scanned inside — orders of magnitude faster than a python loop on CPU, and
the compiled function is reused across clients and rounds (same shapes).

Two entry points:

* :func:`make_local_update` — one client per call (the original path, kept
  for tests/benchmarks and as the numerical reference).
* :func:`make_batched_local_update` — ALL active clients of a round at
  once: batch tensors are stacked to [K, n_steps, B, ...] and one jitted
  ``vmap``-over-clients ``lax.scan`` trains every client in a single
  compiled program (see docs/round_engine.md).  FedProx anchoring,
  quantized forwards, and DP privatization of the uploads all run inside
  the jitted path; an optional mesh shards the leading client axis across
  devices (``shard_map``) so clients train data-parallel.

Supports: plain SGD (FedAvg), proximal term (FedProx, Appendix B), arbitrary
optimizers (the paper's Adam-local-training ablation, Table 6), BatchNorm
running-stats maintenance, and a quantize transform for low-bit clients
(Table 4, straight-through estimator).
"""
from __future__ import annotations

import weakref
from typing import Callable, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.obs.metrics import REGISTRY
from repro.common.pytree import tree_sq_dist
from repro.core.nets import Net
from repro.optim.optimizers import Optimizer, apply_updates

# Counts TRACES of the batched client update (the python side effect only
# fires when jax re-traces, i.e. compiles a new program) — the bucketing
# tests' evidence that compile count stays bounded by buckets x prototypes
# per run instead of growing with rng-driven cohort shapes.  Registered
# in the unified metrics registry; this module-level alias keeps the
# historic reset()/.count interface for tests.
CLIENT_COMPILES = REGISTRY.counter("core.client.compiles")


def softmax_xent(logits: jax.Array, labels: jax.Array) -> jax.Array:
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    return -jnp.mean(jnp.take_along_axis(logp, labels[..., None], -1))


def make_local_update(net: Net, opt: Optimizer, *, prox_mu: float = 0.0,
                      quantize: Optional[Callable] = None):
    """Returns jit'd fn(params, xb [n,B,...], yb [n,B], anchor) -> params.

    ``anchor`` is the round's global model (FedProx pulls towards it; pass
    the initial params when prox_mu == 0, it is ignored).
    """

    def loss_fn(params, x, y):
        p = quantize(params) if quantize is not None else params
        logits, stats = net.apply_with_stats(p, x)
        loss = softmax_xent(logits, y)
        return loss, stats

    @jax.jit
    def run(params, xb, yb, anchor):
        state = opt.init(params)
        mask = net.trainable_mask(params)

        def step(carry, batch):
            params, state, i = carry
            x, y = batch

            def total_loss(p):
                loss, stats = loss_fn(p, x, y)
                if prox_mu > 0.0:
                    loss = loss + 0.5 * prox_mu * tree_sq_dist(p, anchor)
                return loss, stats

            grads, stats = jax.grad(total_loss, has_aux=True)(params)
            grads = jax.tree.map(lambda g, m: g if m else jnp.zeros_like(g),
                                 grads, mask)
            deltas, state = opt.update(grads, state, params, i)
            new_params = apply_updates(params, deltas)
            # take BN running stats from the forward pass (non-trainable)
            new_params = jax.tree.map(
                lambda new, st, m: new if m else st.astype(new.dtype),
                new_params, stats, mask)
            return (new_params, state, i + 1), None

        (params, _, _), _ = jax.lax.scan(step, (params, state, jnp.int32(0)),
                                         (xb, yb))
        return params

    return run


def make_batched_local_update(net: Net, opt: Optimizer, *,
                              prox_mu: float = 0.0,
                              quantize: Optional[Callable] = None,
                              dp_clip: Optional[float] = None,
                              dp_noise_multiplier: float = 0.0,
                              mesh=None, client_axis: str = "data",
                              donate_batches: bool = False):
    """Vectorized local training for all K active clients of a round.

    Returns jit'd ``fn(params, xb [K,n,B,...], yb [K,n,B], anchor,
    step_mask [K,n], dp_keys [K,2]) -> stacked params [K, ...]``.

    ``step_mask`` pads clients with fewer local steps: masked steps leave
    params, optimizer state, and the step counter untouched, so each
    client's trajectory is numerically identical to the sequential
    :func:`make_local_update` run on its own (unpadded) batches.

    When ``dp_clip`` is set, every client's upload is clipped + noised
    (``core/privacy.py``) inside the same jitted program, keyed per client
    by ``dp_keys``.  With a ``mesh``, the leading client axis is sharded
    over ``client_axis`` via ``shard_map`` (K must divide the axis size)
    so clients train data-parallel across devices.

    ``donate_batches=True`` donates the per-round scratch tensors
    (``xb``/``yb``/``step_mask``/``dp_keys``) so XLA reuses their (large)
    buffers instead of reallocating every round — the engine rebuilds
    them each round and never reads them back.  ``params``/``anchor`` are
    deliberately NOT donated: the engine passes the same globals buffer
    to every group and reads it again after training.  Callers that reuse
    their batch arrays across calls (benchmarks) must keep the default.
    """

    def loss_fn(params, x, y):
        p = quantize(params) if quantize is not None else params
        logits, stats = net.apply_with_stats(p, x)
        loss = softmax_xent(logits, y)
        return loss, stats

    def one_client(params, xb, yb, anchor, step_mask, dp_key):
        state = opt.init(params)
        mask = net.trainable_mask(params)

        def step(carry, batch):
            params, state, i = carry
            x, y, valid = batch

            def total_loss(p):
                loss, stats = loss_fn(p, x, y)
                if prox_mu > 0.0:
                    loss = loss + 0.5 * prox_mu * tree_sq_dist(p, anchor)
                return loss, stats

            grads, stats = jax.grad(total_loss, has_aux=True)(params)
            grads = jax.tree.map(lambda g, m: g if m else jnp.zeros_like(g),
                                 grads, mask)
            deltas, new_state = opt.update(grads, state, params, i)
            new_params = apply_updates(params, deltas)
            new_params = jax.tree.map(
                lambda new, st, m: new if m else st.astype(new.dtype),
                new_params, stats, mask)
            # padded steps are no-ops: keep the whole carry unchanged
            keep = lambda n, o: jnp.where(valid, n, o)
            params = jax.tree.map(keep, new_params, params)
            state = jax.tree.map(keep, new_state, state)
            return (params, state, jnp.where(valid, i + 1, i)), None

        (params, _, _), _ = jax.lax.scan(step, (params, state, jnp.int32(0)),
                                         (xb, yb, step_mask))
        if dp_clip is not None:
            from repro.core.privacy import privatize_update
            params = privatize_update(anchor, params, clip=dp_clip,
                                      noise_multiplier=dp_noise_multiplier,
                                      key=dp_key)
        return params

    batched = jax.vmap(one_client, in_axes=(None, 0, 0, None, 0, 0))

    if mesh is not None:
        from jax.sharding import PartitionSpec as P
        rep, cl = P(), P(client_axis)
        batched = jax.shard_map(batched, mesh=mesh,
                                in_specs=(rep, cl, cl, rep, cl, cl),
                                out_specs=cl, check_vma=False)

    def counted(params, xb, yb, anchor, step_mask, dp_keys):
        CLIENT_COMPILES.add(1)  # trace-time side effect: counts compiles
        return batched(params, xb, yb, anchor, step_mask, dp_keys)

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
    the END and masked out, preserving step-for-step equivalence with the
    sequential path.  Pass a fixed ``n_steps`` (max over ALL clients) so
    every round reuses one compiled program.
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
# step-count bucketing (docs/bucketing.md)
#
# Padding every client of a prototype group to the group-wide maximum scan
# length is what makes ONE compiled program per prototype possible, but on
# a skewed Dirichlet split the largest client can have 10-50x the steps of
# the median, so most vmapped lanes burn masked no-op FLOPs.  Bucketing
# partitions the clients into a small FIXED set of step capacities
# (computed once per run from the static per-client step counts) and runs
# one vmapped scan per bucket: a 10-step client no longer scans 500 padded
# steps, and the compile count stays bounded by buckets x prototypes.
# ---------------------------------------------------------------------------


def bucket_capacities(step_counts: Sequence[int], kind: str,
                      max_buckets: int = 4) -> List[int]:
    """The run-fixed set of scan-length capacities for one prototype group.

    Returns an ascending list whose LAST entry is exactly
    ``max(step_counts)`` (so a single bucket reproduces the unbucketed
    path bit-for-bit) and whose length is ``<= max_buckets``.

    ``pow2``      capacities are powers of two clipped at the maximum; when
                  that yields more than ``max_buckets``, the LARGEST
                  capacities are kept (small clients fall into bigger
                  buckets — more padding, never a truncated scan).
    ``quantile``  capacities at ``max_buckets`` evenly-spaced quantiles of
                  the step-count distribution (always including the max).
    ``none``      the single group-wide maximum: today's padded path.
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
    """Index of the smallest capacity holding each client's step count."""
    idx = np.searchsorted(np.asarray(caps), np.asarray(step_counts),
                          side="left")
    if (idx >= len(caps)).any():
        raise ValueError(f"step count(s) exceed the largest bucket "
                         f"capacity {caps[-1]}")
    return idx


def build_bucketed_batches(
        x: np.ndarray, y: np.ndarray, parts: Sequence[np.ndarray],
        batch_size: int, epochs: int, seeds: Sequence[int],
        caps: Sequence[int],
) -> List[Tuple[int, np.ndarray, np.ndarray, np.ndarray, np.ndarray]]:
    """Bucketed variant of :func:`build_batched_batches`.

    Partitions the clients over the run-fixed ``caps`` (ascending scan
    capacities, see :func:`bucket_capacities`) and stacks each bucket's
    scanned batches separately, padded only to the BUCKET's capacity.

    Returns one ``(bucket_index, positions, xb, yb, step_mask)`` tuple per
    non-empty bucket, where ``positions`` are the clients' indices into
    ``parts`` — each client's batch stream is byte-identical to the one
    :func:`build_batched_batches` builds (same per-client seeds, same
    order), only the zero-padded tail is shorter.
    """
    steps = [n_local_steps(len(idx), batch_size, epochs) for idx in parts]
    which = assign_buckets(steps, caps)
    out = []
    for b in range(len(caps)):
        pos = np.flatnonzero(which == b)
        if not len(pos):
            continue
        xb, yb, mask = build_batched_batches(
            x, y, [parts[i] for i in pos], batch_size, epochs,
            seeds=[seeds[i] for i in pos], n_steps=int(caps[b]))
        out.append((b, pos, xb, yb, mask))
    return out


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
