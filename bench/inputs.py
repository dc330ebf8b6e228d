"""Every input of a run, made from ``--seed`` by the benchmark's own
generators: the token task, its train/val/test split, the Dirichlet
partition over clients, and the unlabeled distillation pool.

The generators follow the program's (``data/synthetic.py:token_sequences``,
``data/synthetic.py:train_val_test_split``, ``data/partition.py``) in what
they draw, with two changes that make every seed do the same work:

* tokens are drawn per class in one vectorised call, not row by row, so a
  30,522-token vocabulary costs seconds, not minutes;
* the client sizes are one fixed multiset (a Dirichlet draw from the
  traffic file's ``size_seed``), and ``--seed`` only decides which client
  holds which size and which classes it holds.  The program pads every
  client to the largest client's step count, so the largest size fixes a
  round's work: with sizes drawn per seed, the seed would change the work.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

# independent streams of one seed
_TASK, _POOL, _SPLIT, _PART, _SIZES = range(5)


def rng_for(seed: int, stream: int) -> np.random.Generator:
    return np.random.default_rng(np.random.SeedSequence([int(seed), stream]))


@dataclasses.dataclass
class Split:
    x: np.ndarray  # [n, seq_len] int32 token ids
    y: np.ndarray  # [n] int64 labels


@dataclasses.dataclass
class Inputs:
    train: Split
    val: Split
    test: Split
    parts: List[np.ndarray]  # sorted train indices per client
    pool: np.ndarray         # [n_pool, seq_len] unlabeled token ids


def token_sequences(rng: np.random.Generator, n: int, n_classes: int,
                    vocab: int, seq_len: int, marker_rate: float
                    ) -> Split:
    """Class c draws its tokens from its own unigram distribution over the
    first ``vocab - n_classes`` ids (Dirichlet(0.5)), and each position is
    replaced by the class's marker token ``vocab - n_classes + c`` with
    probability ``marker_rate``."""
    n_plain = vocab - n_classes
    base = rng.dirichlet(np.full(n_plain, 0.5), size=n_classes)
    y = rng.integers(0, n_classes, size=n)
    x = np.empty((n, seq_len), np.int32)
    for c in range(n_classes):
        rows = np.flatnonzero(y == c)
        toks = rng.choice(n_plain, size=(len(rows), seq_len), p=base[c])
        marks = rng.random((len(rows), seq_len)) < marker_rate
        toks[marks] = n_plain + c
        x[rows] = toks
    return Split(x, y.astype(np.int64))


def client_sizes(n_train: int, n_clients: int, alpha: float, min_size: int,
                 size_seed: int) -> np.ndarray:
    """The fixed multiset of client dataset sizes: a Dirichlet(alpha) share
    of the training set per client, at least ``min_size`` each, summing to
    ``n_train``.  Depends on the traffic file only, never on ``--seed``."""
    rng = rng_for(size_seed, _SIZES)
    spare = n_train - n_clients * min_size
    if spare < 0:
        raise ValueError(f"{n_clients} clients of at least {min_size} "
                         f"samples need more than {n_train} samples")
    share = np.floor(rng.dirichlet(np.full(n_clients, alpha)) * spare)
    sizes = share.astype(np.int64) + min_size
    sizes[np.argmax(sizes)] += n_train - int(sizes.sum())
    return np.sort(sizes)[::-1]


def partition(labels: np.ndarray, sizes: np.ndarray, n_classes: int,
              alpha: float, rng: np.random.Generator, n_groups: int = 1
              ) -> List[np.ndarray]:
    """Disjoint client index sets of exactly ``sizes``, each client's class
    mix drawn from Dirichlet(alpha): it takes its share of each class
    while that class lasts and fills the rest from the classes with most
    samples left.  Client k belongs to group k % n_groups (its model);
    the sizes, largest first, are dealt to the groups in turn and drawn
    to the group's clients in a seed-drawn order, so every group holds
    the same sizes under every seed."""
    k = len(sizes)
    order = np.empty(k, np.int64)
    for g in range(n_groups):
        members = np.arange(g, k, n_groups)
        order[g::n_groups] = members[rng.permutation(len(members))]
    by_class = [list(rng.permutation(np.flatnonzero(labels == c)))
                for c in range(n_classes)]
    props = rng.dirichlet(np.full(n_classes, alpha), size=k)
    parts: List[np.ndarray] = [np.empty(0, np.int64)] * k
    for client, size in zip(order, sizes):
        want = np.floor(props[client] * size).astype(np.int64)
        take = []
        for c in range(n_classes):
            m = min(int(want[c]), len(by_class[c]))
            take += by_class[c][:m]
            by_class[c] = by_class[c][m:]
        while len(take) < size:
            c = max(range(n_classes), key=lambda j: len(by_class[j]))
            m = min(int(size) - len(take), len(by_class[c]))
            take += by_class[c][:m]
            by_class[c] = by_class[c][m:]
        parts[client] = np.sort(np.asarray(take, np.int64))
    return parts


def make_inputs(seed: int, model: dict, traffic: dict, n_groups: int = 1
                ) -> Inputs:
    """All inputs of one run.  ``model`` gives the vocabulary, classes and
    sequence length; ``traffic`` the sample counts, split, skew and pool;
    ``n_groups`` the number of client models (see :func:`partition`)."""
    vocab, n_classes = int(model["vocab_size"]), int(model["n_classes"])
    seq_len = int(model["seq_len"])
    task = token_sequences(rng_for(seed, _TASK), int(traffic["n_samples"]),
                           n_classes, vocab, seq_len,
                           float(traffic["marker_rate"]))
    # the pool comes from another draw of class distributions: unlabeled,
    # out-of-domain data, as the paper distils on
    pool = token_sequences(rng_for(seed, _POOL), int(traffic["pool"]),
                           n_classes, vocab, seq_len,
                           float(traffic["marker_rate"])).x
    n = len(task.y)
    idx = rng_for(seed, _SPLIT).permutation(n)
    n_test = int(n * float(traffic["test_frac"]))
    n_val = int(n * float(traffic["val_frac"]))
    pick = lambda ix: Split(task.x[ix], task.y[ix])
    train = pick(idx[n_test + n_val:])
    sizes = client_sizes(len(train.y), int(traffic["n_clients"]),
                         float(traffic["alpha"]),
                         int(traffic["local_batch_size"]),
                         int(traffic["size_seed"]))
    parts = partition(train.y, sizes, n_classes, float(traffic["alpha"]),
                      rng_for(seed, _PART), n_groups)
    return Inputs(train=train, val=pick(idx[n_test:n_test + n_val]),
                  test=pick(idx[:n_test]), parts=parts, pool=pool)
