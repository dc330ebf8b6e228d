"""Plain reference of the first FedDF / FedAvg round, in ``jax.numpy``.

It imports nothing of the program and takes nothing the program made: it
draws its own weights from the seed, trains its own clients, builds its
own logit bank and distils its own student, from the same inputs and by
the same published equations (the paper's Algorithms 1-3, the model's
layer equations, Adam, the cosine schedule).  The model comes from the
configuration's model kind (``bench/models/<kind>.py``: its weights,
forward pass and auxiliary training loss); the round is this file's.  It
runs in float32 at ``highest`` matmul precision; ``dtype="bfloat16"``
gives the lower-precision control, which runs the same code with
parameters, optimizer state and activations in bfloat16.

It holds one model's training state on the device at a time, so that a
client that fills a chip can be checked once the program's state is
freed.  On the host: each group's initial global, every finished
client's weights, the student's start and its best checkpoint.  On the
device: the client or student in training with its Adam moments, or the
data-weighted mean, built leaf by leaf from the host copies, or one
teacher for its logits; beside them the pool, the bank rows and one
512-row block of evaluation rows.  Each step adds its new weights and
moments, a gradient and its activations: the steps donate nothing (a
donated buffer changes how the TPU rounds some leaves' updates), and the
loops let a step run ahead of the host only where memory is plentiful
(``Steps``).  ``RoundOne.live_bytes_max`` is the largest total of live
device arrays after a step.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

EVAL_BLOCK = 512
ADAM_B1, ADAM_B2, ADAM_EPS = 0.9, 0.999, 1e-8


# -- Adam (Kingma & Ba), as the paper trains clients and the student ----------

def _adam(params, m, v, grads, step, lr, cast):
    """One Adam step with bias correction; ``step`` counts from 0 and
    ``cast`` puts a float32 scalar into the working precision."""
    t = step + 1.0
    m = jax.tree.map(lambda a, g: ADAM_B1 * a + (1 - ADAM_B1) * g, m, grads)
    v = jax.tree.map(lambda a, g: ADAM_B2 * a + (1 - ADAM_B2) * g * g, v,
                     grads)
    mh, vh = cast(1.0 - ADAM_B1 ** t), cast(1.0 - ADAM_B2 ** t)
    params = jax.tree.map(
        lambda p, a, b: (p - lr * (a / mh) / (jnp.sqrt(b / vh) + ADAM_EPS)
                         ).astype(p.dtype), params, m, v)
    return params, m, v


def cosine_lr(lr: float, total: int, step):
    t = jnp.clip(step / max(total, 1), 0.0, 1.0)
    return lr * 0.5 * (1.0 + jnp.cos(jnp.pi * t))


class Model:
    """The jitted pieces of one configuration at one precision: float32 at
    ``highest`` matmul precision, or the bfloat16 control.  ``kind`` is the
    model kind's module; the clients' loss adds its auxiliary term."""

    def __init__(self, model: dict, job: dict, kind, dtype=jnp.float32):
        self.model, self.kind, self.dtype = model, kind, jnp.dtype(dtype)
        prec = "highest" if self.dtype == jnp.float32 else "default"
        local_lr = float(job["local_lr"])
        distill_lr = float(job.get("distill_lr", 1e-3))
        steps = int(job.get("distill_steps", 1))
        temp = float(job.get("temperature", 1.0))
        batch = int(job.get("distill_batch", 1))
        cast = lambda a: jnp.asarray(a, jnp.float32).astype(self.dtype)

        def fwd_aux(params, x):
            with jax.default_matmul_precision(prec):
                return kind.forward(params, x, model)

        fwd = lambda params, x: fwd_aux(params, x)[0]

        def client_loss(params, x, y):
            logits, aux = fwd_aux(params, x)
            logp = jax.nn.log_softmax(logits, axis=-1)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], -1)) + aux

        def client_step(params, m, v, x, y, step):
            grads = jax.grad(client_loss)(params, x, y)
            norms = jax.tree.map(lambda g: jnp.sqrt(jnp.sum(jnp.square(
                g.astype(jnp.float32)))), grads)
            params, m, v = _adam(params, m, v, grads, step, cast(local_lr),
                                 cast)
            return params, m, v, norms

        def kl(params, x, t_rows):
            logp_t = jax.nn.log_softmax(t_rows.astype(self.dtype) / temp, -1)
            logp_s = jax.nn.log_softmax(fwd(params, x) / temp, -1)
            return jnp.mean(jnp.sum(jnp.exp(logp_t) * (logp_t - logp_s),
                                    -1)) * temp ** 2

        def distill_step(params, m, v, key, step, pool, bank):
            key, k1 = jax.random.split(key)
            idx = jax.random.randint(k1, (batch,), 0, pool.shape[0])
            grads = jax.grad(kl)(params, pool[idx], bank[idx])
            params, m, v = _adam(params, m, v, grads, step,
                                 cast(cosine_lr(distill_lr, steps, step)),
                                 cast)
            return params, m, v, key

        self.client_step = jax.jit(client_step)
        self.distill_step = jax.jit(distill_step)
        self.logits = jax.jit(lambda p, x: fwd(p, x).astype(jnp.float32))

    def init(self, seed: int) -> dict:
        return self.kind.init_params(jax.random.PRNGKey(seed), self.model,
                                     self.dtype)

    def predict(self, params, x: np.ndarray) -> np.ndarray:
        """Logits [n, C] in float32, 512 rows at a time (the last block
        padded, so that one program serves every block)."""
        out = []
        for s in range(0, len(x), EVAL_BLOCK):
            xb = x[s:s + EVAL_BLOCK]
            pad = EVAL_BLOCK - len(xb)
            if pad:
                xb = np.concatenate([xb, np.zeros((pad,) + xb.shape[1:],
                                                  xb.dtype)])
            out.append(np.asarray(self.logits(params, jnp.asarray(xb)))
                       [:EVAL_BLOCK - pad])
        return np.concatenate(out)

    def accuracy(self, params, x: np.ndarray, y: np.ndarray) -> float:
        return float(np.mean(np.argmax(self.predict(params, x), -1) == y))


# -- the round ----------------------------------------------------------------

def client_batches(x: np.ndarray, y: np.ndarray, batch: int, epochs: int,
                   seed: int):
    """A client's local steps: per epoch one permutation of its rows cut
    into whole batches (one resampled batch when it has fewer rows)."""
    rng = np.random.default_rng(seed)
    n = len(y)
    per_epoch = max(1, n // batch)
    for _ in range(epochs):
        order = (rng.permutation(n)[:per_epoch * batch] if n >= batch
                 else rng.choice(n, size=batch, replace=True))
        for s in range(per_epoch):
            ix = order[s * batch:(s + 1) * batch]
            yield x[ix], y[ix]


def weighted_mean(trees: List[dict], weights) -> dict:
    """The weighted mean of host trees, on the device: one leaf's arrays
    there at a time."""
    w = np.asarray(weights, np.float64)
    w = jnp.asarray(w / w.sum(), jnp.float32)

    def leaf(*xs):
        xs = [jnp.asarray(a) for a in xs]
        return sum(wi * a.astype(jnp.float32) for wi, a in zip(w, xs)
                   ).astype(xs[0].dtype)
    return jax.tree.map(leaf, *trees)


def leaf_norms(tree: dict, base: Optional[dict] = None) -> Dict[str, float]:
    """Per-leaf L2 norm of ``tree`` (minus ``base``), keyed by leaf path,
    on the device; a host tree goes there one leaf at a time."""
    out = {}
    base_leaves = (jax.tree_util.tree_leaves_with_path(base)
                   if base is not None else None)
    for i, (path, a) in enumerate(jax.tree_util.tree_leaves_with_path(tree)):
        a = jnp.asarray(a).astype(jnp.float32)
        if base_leaves is not None:
            a = a - jnp.asarray(base_leaves[i][1]).astype(jnp.float32)
        out[jax.tree_util.keystr(path)] = float(jnp.sqrt(jnp.sum(a * a)))
    return out


@dataclasses.dataclass
class RoundOne:
    """What round 1 produced, in the terms the comparison reads."""
    clients: List[List[Dict[str, float]]]  # per group, per client: |change|
    first_grad: Dict[str, float]           # first client's first gradient
    bank: Optional[np.ndarray]             # [n_pool, C] averaged logits
    fused: List[Dict[str, float]]          # per group: |fused - init|
    chunks: List[Dict[str, Dict[str, float]]]  # per distillation: its
    #   first eval_every steps' |change| and Adam's gradient norm per leaf
    distilled: List[bool]                  # per group: went through distill
    test_acc: List[float]
    val_acc: List[float]
    pre_acc: List[Optional[float]]
    ens_acc: Optional[float]
    seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    val_history: List[list] = dataclasses.field(default_factory=list)
    live_bytes_max: int = 0  # live device bytes, largest after a step


class Steps:
    """Paces a loop's steps and keeps the largest total of the process's
    live device arrays seen between them.  Where a training state
    (weights and two moments, ``state_bytes``) takes under an eighth of
    the device's memory, the host waits for the step before the one it
    has just dispatched, so that the chip has the next step queued;
    otherwise it waits for each step, as a step queued ahead holds its
    outputs beside the running one's."""

    def __init__(self, state_bytes: int):
        limit = (jax.devices()[0].memory_stats() or {}).get("bytes_limit")
        self.ahead = limit is None or 8 * state_bytes < limit
        self.live_max = 0
        self._last = None

    def settle(self, marker) -> None:
        """``marker``: a small output of the step just dispatched (all of
        a step's outputs are ready at once)."""
        if not self.ahead:
            jax.block_until_ready(marker)
        elif self._last is not None:
            jax.block_until_ready(self._last)
        self._last = marker
        self.live_max = max(self.live_max,
                            sum(a.nbytes for a in jax.live_arrays()))


def round_one(models: List[Model], job: dict, inputs, proto: List[int],
              seed: int, heterogeneous: bool) -> RoundOne:
    """Algorithm 1 (one group) or Algorithm 3 (several) for round t=1:
    the cohort draw, each client's local Adam steps from the group's
    initial global, the data-weighted mean, and for FedDF the teacher
    bank over the pool and each student's distillation with its
    best-on-validation checkpoint."""
    clock = [time.perf_counter()]
    seconds: Dict[str, float] = {}

    def lap(name):  # every phase ends in host values, so the clock is fair
        now = time.perf_counter()
        seconds[name] = round(now - clock[0], 3)
        clock[0] = now

    t = 1
    n_clients = len(inputs.parts)
    n_active = max(1, int(round(float(job["client_fraction"]) * n_clients)))
    active = np.random.default_rng(seed).choice(n_clients, size=n_active,
                                                replace=False)
    mult = 99991 if heterogeneous else 100_003
    g0 = [jax.device_get(m.init(seed + p if heterogeneous else seed))
          for p, m in enumerate(models)]
    waits = Steps(3 * max(sum(a.nbytes for a in jax.tree.leaves(g))
                          for g in g0))
    feddf = job["strategy"] == "feddf"
    batch, epochs = int(job["local_batch_size"]), int(job["local_epochs"])

    clients, first_grad = [], None
    stacks: List[List[dict]] = [[] for _ in models]
    weights: List[List[float]] = [[] for _ in models]
    for p, m in enumerate(models):
        rows = []
        for k in [int(k) for k in active if proto[int(k)] == p]:
            part = inputs.parts[k]
            params = jax.device_put(g0[p])
            mom = jax.tree.map(jnp.zeros_like, params)
            vel = jax.tree.map(jnp.zeros_like, params)
            for i, (xb, yb) in enumerate(client_batches(
                    inputs.train.x[part], inputs.train.y[part], batch,
                    epochs, seed * mult + t * 131 + k)):
                params, mom, vel, norms = m.client_step(
                    params, mom, vel, jnp.asarray(xb), jnp.asarray(yb),
                    jnp.float32(i))
                waits.settle(norms)
                if first_grad is None:
                    first_grad = {
                        jax.tree_util.keystr(path): float(g) for path, g in
                        jax.tree_util.tree_leaves_with_path(norms)}
            rows.append(leaf_norms(params, g0[p]))
            stacks[p].append(jax.device_get(params))
            weights[p].append(float(len(part)))
            del params, mom, vel
        clients.append(rows)
    lap("clients")

    ens_acc = None
    if heterogeneous:
        tot = None
        for p, m in enumerate(models):
            for params in stacks[p]:
                lg = m.predict(jax.device_put(params), inputs.test.x)
                tot = lg if tot is None else tot + lg
        ens_acc = float(np.mean(np.argmax(tot, -1) == inputs.test.y))

    bank = None
    if feddf:
        pool = inputs.pool
        bank = np.zeros((len(pool), int(models[0].model["n_classes"])),
                        np.float64)
        n_teach = 0
        for p, m in enumerate(models):
            for params in stacks[p]:
                bank += m.predict(jax.device_put(params), pool)
                n_teach += 1
        bank = (bank / n_teach).astype(np.float32)
        lap("bank")

    fused, distilled, chunks = [], [], []
    test_acc, val_acc, pre_acc, history = [], [], [], []
    for p, m in enumerate(models):
        if not stacks[p]:
            fused.append(leaf_norms(g0[p], g0[p]))
            distilled.append(False)
            start = jax.device_put(g0[p])
            test_acc.append(m.accuracy(start, inputs.test.x, inputs.test.y))
            val_acc.append(m.accuracy(start, inputs.val.x, inputs.val.y))
            pre_acc.append(None)
            del start
            continue
        avg = weighted_mean(stacks[p], weights[p])
        if feddf:
            pre_acc.append(None if heterogeneous else
                           m.accuracy(avg, inputs.test.x, inputs.test.y))
            start = jax.device_get(avg)
            del avg  # the student starts from the host copy
            out, hist, first = _distill(m, start, inputs, bank, job,
                                        seed + t + (p if heterogeneous else 0),
                                        waits)
            out = jax.device_put(out)
            history.append(hist)
            chunks.append(first)
        else:
            pre_acc.append(None)
            out = avg
        fused.append(leaf_norms(out, g0[p]))
        distilled.append(feddf)
        test_acc.append(m.accuracy(out, inputs.test.x, inputs.test.y))
        val_acc.append(m.accuracy(out, inputs.val.x, inputs.val.y))
        del out
    lap("fusion_and_evaluation")
    return RoundOne(clients=clients, first_grad=first_grad or {}, bank=bank,
                    fused=fused, distilled=distilled, chunks=chunks,
                    test_acc=test_acc, val_acc=val_acc, pre_acc=pre_acc,
                    ens_acc=ens_acc, seconds=seconds, val_history=history,
                    live_bytes_max=waits.live_max)


def _distill(m: Model, student: dict, inputs, bank: np.ndarray, job: dict,
             seed: int, waits: Steps):
    """Adam on KL(softmax(bank row) || softmax(student)) over batches drawn
    from the pool, checking validation accuracy every ``eval_every`` steps
    and keeping the best checkpoint (strictly better replaces; the
    starting student is never kept).  ``student`` and the best checkpoint
    are host trees.  Returns (best; the validation accuracies; the first
    check's per-leaf change from ``student`` and gradient norm as Adam's
    bias-corrected second moment holds it)."""
    steps, every = int(job["distill_steps"]), int(job["eval_every"])
    pool = jnp.asarray(inputs.pool)
    rows = jnp.asarray(bank)
    params = jax.device_put(student)
    mom = jax.tree.map(jnp.zeros_like, params)
    vel = jax.tree.map(jnp.zeros_like, params)
    key = jax.random.PRNGKey(seed)
    best, best_acc, hist, first = student, -1.0, [], None
    for step in range(steps):
        params, mom, vel, key = m.distill_step(
            params, mom, vel, key, jnp.float32(step), pool, rows)
        waits.settle(key)
        if step + 1 == every:
            corr = 1.0 - ADAM_B2 ** every
            first = {"change": leaf_norms(params, student),
                     "grad": {jax.tree_util.keystr(path): float(jnp.sqrt(
                         jnp.sum(v.astype(jnp.float32)) / corr))
                         for path, v in
                         jax.tree_util.tree_leaves_with_path(vel)}}
        if (step + 1) % every == 0:
            acc = m.accuracy(params, inputs.val.x, inputs.val.y)
            hist.append([step + 1, acc])
            if acc > best_acc:
                best, best_acc = jax.device_get(params), acc
    return best, hist, first
