"""The batched client update trains each client for its own step count.

 1. Per client, the batched update equals :func:`make_local_update` run on
    that client's own unpadded batches, for step counts 0 (a padded slot),
    1, mid and the full capacity: Adam, SGD, FedProx, DP, and the client
    axis under ``shard_map`` on four CPU devices (subprocess).
 2. A zero-step padded slot returns the initial global.
 3. Cohorts with other step counts reuse the one compiled program per
    prototype.
 4. The ``train_clients`` span and the ``core.client.real_steps``
    counter report the client-steps the cohort holds, below the padded
    batch slots on a skewed split.
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core import FLConfig, mlp
from repro.core.client import (CLIENT_COMPILES, REAL_STEPS,
                               build_batches, make_batched_local_update,
                               make_local_update, n_local_steps, trip_counts)
from repro.core.engine import RoundEngine
from repro.core.privacy import privatize_update
from repro.data import dirichlet_partition, gaussian_mixture, \
    train_val_test_split
from repro.obs import trace
from repro.optim.optimizers import adam, sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

B = 8
# client slots: a padded slot (no data), then 1, 5 and 12 steps at B=8
SIZES = (None, 8, 40, 96)
CAP = n_local_steps(96, B, 1)
DP = (1.0, 0.3)


def _problem():
    rng = np.random.default_rng(0)
    n = sum(s for s in SIZES if s)
    x = rng.normal(size=(n, 2)).astype(np.float32)
    y = rng.integers(0, 3, size=n)
    parts, off = [], 0
    for s in SIZES:
        parts.append(None if s is None else np.arange(off, off + s))
        off += s or 0
    net = mlp(2, 3, hidden=(16,), norm="bn")
    return net, net.init(jax.random.PRNGKey(0)), x, y, parts


def _stacked_batches(x, y, parts):
    """[K, CAP, B, ...] batches with prefix masks, each client's stream
    from :func:`build_batches`; the padded slot is all zeros."""
    k = len(parts)
    xb = np.zeros((k, CAP, B, x.shape[1]), x.dtype)
    yb = np.zeros((k, CAP, B), y.dtype)
    mask = np.zeros((k, CAP), bool)
    own = []
    for i, idx in enumerate(parts):
        if idx is None:
            own.append(None)
            continue
        xi, yi = build_batches(x[idx], y[idx], B, 1, seed=i)
        xb[i, :len(xi)], yb[i, :len(yi)], mask[i, :len(xi)] = xi, yi, True
        own.append((xi, yi))
    return xb, yb, mask, own


def max_gap(variant: str, mesh=None) -> float:
    """Largest gap, over clients and leaves, between the batched update
    and the sequential reference on each client's own batches."""
    net, g, x, y, parts = _problem()
    opt = adam(1e-2) if variant == "adam" else sgd(0.05)
    prox = 0.5 if variant == "prox" else 0.0
    dp = DP if variant == "dp" else None
    keys = [jax.random.PRNGKey(100 + k) for k in range(len(parts))]
    xb, yb, mask, own = _stacked_batches(x, y, parts)
    assert sorted(trip_counts(mask).tolist()) == [0, 1, 5, CAP]

    upd = make_local_update(net, opt, prox_mu=prox)
    seq = []
    for k, batches in enumerate(own):
        p = g if batches is None else upd(g, *map(jnp.asarray, batches), g)
        if dp is not None:
            p = privatize_update(g, p, clip=dp[0], noise_multiplier=dp[1],
                                 key=keys[k])
        seq.append(p)

    kw = {"prox_mu": prox, "mesh": mesh}
    if dp is not None:
        kw["dp_clip"], kw["dp_noise_multiplier"] = dp
    stack = make_batched_local_update(net, opt, **kw)(
        g, jnp.asarray(xb), jnp.asarray(yb), g, jnp.asarray(mask),
        jnp.stack(keys))
    gap = 0.0
    for k, p in enumerate(seq):
        for a, b in zip(jax.tree.leaves(p), jax.tree.leaves(stack)):
            gap = max(gap, float(jnp.max(jnp.abs(a - b[k]))))
    return gap


# SGD and FedProx steps are bit-identical to the sequential scan.  Adam's
# rsqrt chain, and the DP clip and noise jitted with the loop rather than
# run eagerly, fuse otherwise: float32 round-off of a few ulps at the
# parameters' magnitude (about 1), so 1e-6, set from the dtype.
TOL = {"sgd": 0.0, "prox": 0.0, "adam": 1e-6, "dp": 1e-6, "mesh4": 1e-6}


@pytest.mark.parametrize("variant", ["adam", "sgd", "prox", "dp", "mesh4"])
def test_loop_matches_sequential_per_client(variant):
    if variant != "mesh4":
        assert max_gap(variant) <= TOL[variant]
        return
    code = f"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path[:0] = [{os.path.join(ROOT, "src")!r}, {os.path.join(ROOT, "tests")!r}]
import jax
from repro.launch.mesh import make_client_mesh
from test_client_loop import max_gap
assert len(jax.devices()) == 4
print("GAP", max_gap("adam", mesh=make_client_mesh(4)))
"""
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    gaps = [float(l.split()[1]) for l in r.stdout.splitlines()
            if l.startswith("GAP ")]
    assert len(gaps) == 1 and gaps[0] <= TOL[variant], r.stdout + r.stderr


def test_zero_step_slot_returns_initial_global():
    net, g, x, y, parts = _problem()
    xb, yb, mask, _ = _stacked_batches(x, y, parts)
    stack = make_batched_local_update(net, adam(1e-2))(
        g, jnp.asarray(xb), jnp.asarray(yb), g, jnp.asarray(mask),
        jnp.zeros((len(parts), 2), jnp.uint32))
    assert not mask[0].any()
    for a, b in zip(jax.tree.leaves(g), jax.tree.leaves(stack)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b[0]))


@pytest.fixture(scope="module")
def skewed():
    """Dirichlet alpha=0.1 over 16 clients: the largest has tens of times
    the median's local steps."""
    ds = gaussian_mixture(3000, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    return train, val, test, dirichlet_partition(train.y, 16, 0.1, seed=0)


def _engine(skewed, **kw):
    train, val, test, parts = skewed
    nets = [mlp(2, 3, hidden=(12,), name="p-s"),
            mlp(2, 3, hidden=(24,), name="p-m")]
    cfg = FLConfig(strategy="fedavg", rounds=2, local_epochs=3,
                   local_batch_size=32, local_lr=0.05, seed=0, **kw)
    return RoundEngine(nets, [k % 2 for k in range(16)], train, parts, val,
                       test, cfg, heterogeneous=True)


def test_other_step_counts_compile_once_per_prototype(skewed):
    engine = _engine(skewed, client_fraction=0.5)
    g = engine.init_globals()
    cohorts = [np.arange(0, 8), np.arange(8, 16)]
    real = []
    CLIENT_COMPILES.reset()
    for t, active in enumerate(cohorts, 1):
        batches = engine.build_round_batches(t, active)
        real.append(sorted(engine.client_steps[k] for k in active))
        jax.block_until_ready([gr.stack for gr in
                               engine.train_clients(t, g, batches)])
    assert real[0] != real[1]
    assert CLIENT_COMPILES.count == engine.n_proto


def test_span_and_counter_report_real_steps(skewed):
    engine = _engine(skewed, client_fraction=1.0)
    batches = engine.build_round_batches(
        1, engine.sample_cohort(engine.make_rng()))
    real = sum(rb.real_steps for rb in batches if rb is not None)
    padded = sum(rb.padded_slots for rb in batches if rb is not None)
    REAL_STEPS.reset()
    rec = trace.arm()
    try:
        engine.train_clients(1, engine.init_globals(), batches)
    finally:
        trace.disarm()
    span, = [s for s in rec.spans if s["name"] == "train_clients"]
    assert span["real_steps"] == REAL_STEPS.count == real
    assert real == sum(engine.client_steps)
    assert real < padded
