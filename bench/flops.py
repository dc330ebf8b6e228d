"""Operations and bytes the algorithm needs, from shapes alone.

A round's model FLOPs come from the model kind (``bench/models/<kind>.py``:
``forward_flops_per_token``, over the active parameters); training adds
the backward pass at twice the forward's cost.
"""
from __future__ import annotations

import numpy as np


def train_flops_per_token(kind, model: dict) -> float:
    return 3.0 * kind.forward_flops_per_token(model)


def kl_bank_cost(b: int, n: int, c: int, bank_itemsize: int):
    """(flops, bytes) of one forward and one backward call of the fused
    bank kernel (``kernels/ensemble_kl.py:ensemble_kl_bank``) on B student
    rows of C logits against an [N, C] bank.

    Forward reads the B sampled indices, the B student rows, the B bank
    rows they index and B per-row scales, and writes three per-row
    statistics.  Backward reads the same, the two saved per-row
    log-sum-exps and the scalar cotangent, and writes the [B, C]
    gradient.  Per element the forward does about 10 operations (scale,
    running maxima, two exps, products and sums), the backward about 6
    (two exps, difference, scale).  N only sets which rows are read."""
    del n
    reads = b * 4 + b * c * 4 + b * c * bank_itemsize + b * 4
    fwd_bytes = reads + 3 * b * 4
    bwd_bytes = reads + 2 * b * 4 + 4 + b * c * 4
    return 16.0 * b * c, float(fwd_bytes + bwd_bytes)


def round_flops(kind, model_of, job: dict, inputs, proto, active) -> dict:
    """Model FLOPs of one round by phase, real work only: the local steps
    of the active clients (not the masked padding), the bank over the
    pool, the distillation steps and the evaluations (distillation's
    validation checks, the pre-distillation and final accuracies, and the
    ensemble accuracy of a heterogeneous round).  ``model_of(p)`` is the
    model dict of prototype ``p``, of the model kind ``kind``."""
    fwd_tok = kind.forward_flops_per_token
    train_tok = lambda m: train_flops_per_token(kind, m)
    batch, epochs = int(job["local_batch_size"]), int(job["local_epochs"])
    out = {"client": 0.0, "bank": 0.0, "distill": 0.0, "eval": 0.0}
    groups = sorted({proto[int(k)] for k in active})
    n_groups = len({int(p) for p in proto})
    hetero = n_groups > 1
    feddf = job["strategy"] == "feddf"
    n_test, n_val = len(inputs.test.y), len(inputs.val.y)
    for k in active:
        m = model_of(proto[int(k)])
        s = int(m["seq_len"])
        steps = epochs * max(1, len(inputs.parts[int(k)]) // batch)
        out["client"] += steps * batch * s * train_tok(m)
        if feddf:
            out["bank"] += len(inputs.pool) * s * fwd_tok(m)
        if hetero:
            out["eval"] += n_test * s * fwd_tok(m)
    for p in range(n_groups):
        m = model_of(p)
        s = int(m["seq_len"])
        fwd = fwd_tok(m) * s
        out["eval"] += (n_test + n_val) * fwd
        if p not in groups or not feddf:
            continue
        steps = int(job["distill_steps"])
        out["distill"] += steps * int(job["distill_batch"]) * s \
            * train_tok(m)
        out["eval"] += (steps // int(job["eval_every"])) * n_val * fwd
        if not hetero:
            out["eval"] += n_test * fwd
    return out


def cohorts(seed: int, n_clients: int, client_fraction: float,
            rounds: int):
    """The active clients of rounds 1..rounds: a uniform draw without
    replacement per round from one generator seeded with the run's
    seed, as the paper's Algorithm 1 samples its cohort."""
    n_active = max(1, int(round(client_fraction * n_clients)))
    rng = np.random.default_rng(seed)
    return [rng.choice(n_clients, size=n_active, replace=False)
            for _ in range(rounds)]
