"""Run-fixed padded capacities (docs/bucketing.md).

 1. Capacity construction (``bucket_capacities``, the distillation axis's
    batch-size bucketing): ascending, bounded by ``max_buckets``, last
    capacity exactly the maximum, every size fits its smallest bucket.
 2. Round batches on a SKEWED Dirichlet alpha=0.1 split: every group's
    tensors pad to its run-fixed client capacity and scan length, each
    client's batch stream is its own, and the engine's stacks equal the
    sequential update on each client's own batches.
 3. Compile count: ``CLIENT_COMPILES`` (a trace-time counter) stays at one
    program per prototype for a whole run.
 4. Mesh divisibility padding: heterogeneous cohorts accept a client mesh
    — per-prototype client capacities pad up to the mesh axis, padded
    slots carry all-False step masks and are sliced off — and per-round
    results equal the unsharded run on a 4-device simulated mesh
    (subprocess with forced host devices).
"""
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.api import ExperimentSpec
from repro.core import FLConfig, FusionConfig, mlp, run_rounds
from repro.core.client import (CLIENT_COMPILES, assign_buckets,
                               bucket_capacities, build_batches,
                               make_local_update)
from repro.core.engine import RoundEngine
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)
from repro.optim.optimizers import sgd

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

K = 16
ALPHA = 0.1


@pytest.fixture(scope="module")
def skewed():
    """Dirichlet alpha=0.1 over K=16 clients: the largest client has tens
    of times the local steps of the median (the padded-tensor case)."""
    ds = gaussian_mixture(3000, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, K, ALPHA, seed=0)
    sizes = sorted(len(p) for p in parts)
    assert sizes[-1] >= 5 * sizes[K // 2]  # really skewed
    src = UnlabeledDataset(np.random.default_rng(1).uniform(
        -3, 3, (500, 2)).astype(np.float32))
    return train, val, test, parts, src


def cfg_for(strategy="fedavg", rounds=2, **kw):
    base = dict(client_fraction=0.5, local_epochs=3, local_batch_size=32,
                local_lr=0.05, seed=0,
                fusion=FusionConfig(max_steps=50, patience=50,
                                    eval_every=25, batch_size=32))
    base.update(kw)
    return FLConfig(strategy=strategy, rounds=rounds, **base)


def _hetero_nets():
    return ([mlp(2, 3, hidden=(12,), name="p-s"),
             mlp(2, 3, hidden=(24,), name="p-m")],
            [k % 2 for k in range(K)])


def _assert_same_run(a, b):
    res_a, glob_a, rtt_a = a
    res_b, glob_b, rtt_b = b
    assert rtt_a == rtt_b
    for ra, rb in zip(res_a, res_b):
        assert ra.logs == rb.logs
    for ga, gb in zip(glob_a, glob_b):
        for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))


# ---------------------------------------------------------------------------
# capacity construction
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("kind", ["pow2", "quantile"])
def test_bucket_capacities_properties(kind):
    rng = np.random.default_rng(0)
    for _ in range(20):
        steps = rng.integers(1, 500, size=rng.integers(1, 40)).tolist()
        for m in (1, 2, 4, 8):
            caps = bucket_capacities(steps, kind, m)
            assert caps == sorted(caps)           # ascending
            assert len(caps) == len(set(caps))    # unique
            assert len(caps) <= m                 # bounded
            assert caps[-1] == max(steps)         # exact max: no extra pad
            which = assign_buckets(steps, caps)
            for s, b in zip(steps, which):
                assert s <= caps[b]               # every client fits
                if b > 0:
                    assert s > caps[b - 1]        # ...in its SMALLEST bucket


def test_bucket_capacities_none_and_degenerate():
    assert bucket_capacities([7, 7, 7], "pow2", 4) == [7]
    assert bucket_capacities([3, 9, 30], "none", 4) == [30]
    assert bucket_capacities([], "pow2", 4) == [1]
    with pytest.raises(ValueError, match="bucket kind"):
        bucket_capacities([1, 2], "fib", 4)
    with pytest.raises(ValueError, match="exceed"):
        assign_buckets([10], [4, 8])


# ---------------------------------------------------------------------------
# round batches on the skewed split
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("hetero", [False, True],
                         ids=["homogeneous", "heterogeneous"])
def test_round_batches_pad_to_run_fixed_shapes(skewed, hetero):
    """Every round's tensors have the run-fixed shape of their group;
    the masks are prefixes, padded client slots all False, and the
    accounting counts the clients' own steps."""
    train, val, test, parts, src = skewed
    nets, proto = _hetero_nets() if hetero else ([mlp(2, 3)], [0] * K)
    engine = RoundEngine(nets, proto, train, parts, val, test, cfg_for(),
                         heterogeneous=hetero)
    rng = engine.make_rng()
    for t in (1, 2, 3):
        batches = engine.build_round_batches(t, engine.sample_cohort(rng))
        for p, rb in enumerate(batches):
            if rb is None:
                continue
            cap = engine.k_cap[p]
            assert rb.xb.shape[:2] == (cap, engine.steps_cap[p])
            assert rb.step_mask.shape == (cap, engine.steps_cap[p])
            assert rb.dp_keys.shape == (cap, 2)
            n = rb.step_mask.sum(axis=1)
            for row, k in enumerate(rb.ks):
                assert n[row] == engine.client_steps[k]
                assert rb.step_mask[row, :n[row]].all()
            assert not rb.step_mask[rb.k_real:].any()
            assert rb.real_steps == sum(engine.client_steps[k]
                                        for k in rb.ks)
            assert rb.padded_slots == cap * engine.steps_cap[p]


@pytest.mark.parametrize("hetero", [False, True],
                         ids=["homogeneous", "heterogeneous"])
def test_round_batches_hold_each_clients_own_stream(skewed, hetero):
    """Each client's masked-in rows are the batches :func:`build_batches`
    draws for it alone, with its round seed: padding only appends."""
    train, val, test, parts, src = skewed
    cfg = cfg_for(seed=3)
    nets, proto = _hetero_nets() if hetero else ([mlp(2, 3)], [0] * K)
    engine = RoundEngine(nets, proto, train, parts, val, test, cfg,
                         heterogeneous=hetero)
    t, active = 2, np.array([0, 5, 9, 13, 2, 7, 11, 15])
    for rb in engine.build_round_batches(t, active):
        for row, k in enumerate(rb.ks):
            xk, yk = build_batches(
                train.x[parts[k]], train.y[parts[k]], 32, 3,
                seed=cfg.seed * engine.batch_seed_mult + t * 131 + k)
            n = len(xk)
            np.testing.assert_array_equal(rb.xb[row, :n], xk)
            np.testing.assert_array_equal(rb.yb[row, :n], yk)
            assert not rb.xb[row, n:].any()


@pytest.mark.parametrize("hetero", [False, True],
                         ids=["homogeneous", "heterogeneous"])
def test_engine_stacks_match_sequential_clients(skewed, hetero):
    """``train_clients`` on padded tensors equals the sequential SGD
    update on each client's own batches, in the group's client order."""
    train, val, test, parts, src = skewed
    nets, proto = _hetero_nets() if hetero else ([mlp(2, 3)], [0] * K)
    engine = RoundEngine(nets, proto, train, parts, val, test, cfg_for(),
                         heterogeneous=hetero)
    g = engine.init_globals()
    batches = engine.build_round_batches(
        1, engine.sample_cohort(engine.make_rng()))
    groups = engine.train_clients(1, g, batches)
    for p, (rb, gr) in enumerate(zip(batches, groups)):
        if rb is None:
            assert gr.stack is None
            continue
        upd = make_local_update(nets[p], sgd(0.05))
        for row, k in enumerate(rb.ks):
            n = engine.client_steps[k]
            want = upd(g[p], jnp.asarray(rb.xb[row, :n]),
                       jnp.asarray(rb.yb[row, :n]), g[p])
            for a, b in zip(jax.tree.leaves(want),
                            jax.tree.leaves(gr.stack)):
                np.testing.assert_array_equal(np.asarray(a),
                                              np.asarray(b[row]))
        assert len(jax.tree.leaves(gr.stack)[0]) == rb.k_real


def test_skewed_async_driver_matches_sync(skewed):
    """Padded batches are prefetched and trained by the async driver
    exactly like the sync driver's (staleness=0 == sync)."""
    from repro.drivers import make_driver
    train, val, test, parts, src = skewed
    net = mlp(2, 3, hidden=(16,))

    def run(driver):
        return run_rounds([net], [0] * K, train, parts, val, test,
                          cfg_for(), driver=driver)

    _assert_same_run(run("sync"),
                     run(make_driver("async_pipelined", staleness=0,
                                     prefetch=2)))


def test_capacity_aware_sampler_sees_one_cell_per_prototype(skewed):
    """The engine hands the cohort sampler one (prototype, 0) cell per
    prototype, filled to the prototype's client capacity."""
    train, val, test, parts, src = skewed
    nets, proto = _hetero_nets()
    cfg = cfg_for()
    cfg.population.sampler = "capacity_aware"
    engine = RoundEngine(nets, proto, train, parts, val, test, cfg,
                         heterogeneous=True)
    ctx = engine.sampler.ctx
    assert not ctx.bucket.any()
    assert ctx.bucket_client_caps == [[c] for c in engine.k_cap]
    active = engine.sample_cohort(engine.make_rng())
    assert len(set(int(a) for a in active)) == engine.n_active
    counts = np.bincount([proto[a] for a in active], minlength=2)
    assert (counts <= np.asarray(engine.k_cap)).all()


def test_experiment_spec_has_no_bucket_axis():
    """Step-count bucketing is gone: a spec naming it fails loudly."""
    d = ExperimentSpec().to_dict()
    assert "bucket" not in d
    d["bucket"] = {"kind": "pow2", "max_buckets": 4}
    with pytest.raises(ValueError, match="bucket"):
        ExperimentSpec.from_dict(d)


# ---------------------------------------------------------------------------
# compile count
# ---------------------------------------------------------------------------

def test_client_compiles_one_per_prototype_heterogeneous(skewed):
    train, val, test, parts, src = skewed
    nets, proto = _hetero_nets()
    CLIENT_COMPILES.reset()
    run_rounds(nets, proto, train, parts, val, test, cfg_for(rounds=3),
               heterogeneous=True)
    assert CLIENT_COMPILES.count == len(nets), CLIENT_COMPILES.count


def test_client_compiles_one_per_prototype_unbucketed(skewed):
    train, val, test, parts, src = skewed
    net = mlp(2, 3, hidden=(16,))
    CLIENT_COMPILES.reset()
    run_rounds([net], [0] * K, train, parts, val, test, cfg_for(rounds=3))
    assert CLIENT_COMPILES.count == 1, CLIENT_COMPILES.count


# ---------------------------------------------------------------------------
# mesh divisibility padding (forced host devices in a subprocess)
# ---------------------------------------------------------------------------

def test_hetero_mesh_matches_unsharded_on_4_devices():
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {src!r})
import jax
import numpy as np
from repro.core import FLConfig, mlp, run_rounds
from repro.data import (dirichlet_partition, gaussian_mixture,
                        train_val_test_split)

assert len(jax.devices()) == 4
ds = gaussian_mixture(2000, n_classes=3, dim=2, seed=0)
train, val, test = train_val_test_split(ds)
parts = dirichlet_partition(train.y, 8, 0.1, seed=0)
nets = [mlp(2, 3, hidden=(12,), name="s"), mlp(2, 3, hidden=(24,), name="m"),
        mlp(2, 3, hidden=(32,), name="l")]
proto = [k % 3 for k in range(8)]  # group sizes 3/3/2: none divide 4

def run(driver):
    cfg = FLConfig(strategy="fedavg", rounds=2, client_fraction=1.0,
                   local_epochs=2, local_batch_size=32, local_lr=0.05,
                   seed=0)
    return run_rounds(nets, proto, train, parts, val, test, cfg,
                      heterogeneous=True, driver=driver)

sync = run("sync")
mh = run("multihost")
assert all(ra.logs == rb.logs for ra, rb in zip(sync[0], mh[0]))
for ga, gb in zip(sync[1], mh[1]):
    for x, y in zip(jax.tree.leaves(ga), jax.tree.leaves(gb)):
        np.testing.assert_array_equal(np.asarray(x), np.asarray(y))
print("HETERO_MESH_OK")
""".format(src=os.path.join(ROOT, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.stdout.count("HETERO_MESH_OK") == 1, r.stdout + r.stderr


def test_padded_clients_masked_under_mesh_padding(skewed):
    """A 1-device mesh exercises the same padded-capacity path: capacities
    round up, the padded slots carry all-False masks, and the output
    equals the meshless run."""
    from repro.launch.mesh import make_client_mesh
    train, val, test, parts, src = skewed
    nets, proto = _hetero_nets()

    engine = RoundEngine(nets, proto, train, parts, val, test, cfg_for(),
                         heterogeneous=True, mesh=make_client_mesh(1))
    batches = engine.build_round_batches(
        1, engine.sample_cohort(engine.make_rng()))
    for rb in batches:
        if rb is None:
            continue
        assert rb.xb.shape[0] == rb.cap_clients
        # every padded slot is fully masked out
        assert not rb.step_mask[rb.k_real:].any()

    base = run_rounds(nets, proto, train, parts, val, test, cfg_for(),
                      heterogeneous=True)
    sharded = run_rounds(nets, proto, train, parts, val, test, cfg_for(),
                         heterogeneous=True, mesh=make_client_mesh(1))
    _assert_same_run(base, sharded)
