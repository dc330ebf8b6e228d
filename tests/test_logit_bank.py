"""Teacher-logit bank fast path (docs/distill_fast_path.md):

 1. Bank-path trajectories numerically match the on-the-fly path —
    homogeneous, heterogeneous (shared bank) and SWAG-augmented teachers,
    with and without validation-based early stopping.
 2. The forward-call counter shows the K×steps (and heterogeneous G×)
    teacher-forward redundancy collapsing to one pass over the pool.
 3. The source pool/index interface holds its contract
    (``sample(key, b) == pool()[sample_indices(key, b)]``); generator /
    noise sources fall back to on-the-fly loudly when the bank is forced.
 4. FusionSpec round-trips + validates the new knobs; ``use_fused_kernel
    = 'auto'`` resolves per backend.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.common.pytree import tree_stack
from repro.core import mlp
from repro.core.feddf import (FusionConfig, distill, expected_distill_steps,
                              feddf_fuse_heterogeneous_stacked,
                              feddf_fuse_stacked, make_teacher_logits_fn)
from repro.core.logit_bank import (PERSISTENT_BANK, TEACHER_FORWARDS,
                                   bank_for_fusion, build_logit_bank,
                                   resolve_bank)
from repro.core.swag import swag_teachers, swag_teachers_stacked
from repro.data.distill_sources import (GeneratorSource, RandomNoiseSource,
                                        UnlabeledDataset)

RNG = np.random.default_rng(0)


def _fusion(**kw):
    base = dict(max_steps=75, patience=1_000, eval_every=25, batch_size=32,
                use_fused_kernel=False)
    base.update(kw)
    return FusionConfig(**base)


def _source(n=400, dim=2, seed=0):
    return UnlabeledDataset(np.random.default_rng(seed).uniform(
        -3, 3, (n, dim)).astype(np.float32))


def _val(n=150, dim=2, classes=3, seed=1):
    r = np.random.default_rng(seed)
    return (r.uniform(-3, 3, (n, dim)).astype(np.float32),
            r.integers(0, classes, size=n))


def _stack(net, k, seed0=0):
    return tree_stack([net.init(jax.random.PRNGKey(seed0 + i))
                       for i in range(k)])


def _assert_trees_close(a, b, atol=5e-5):
    for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
        np.testing.assert_allclose(np.asarray(x), np.asarray(y), atol=atol,
                                   rtol=1e-5)


# ---------------------------------------------------------------------------
# trajectory equivalence
# ---------------------------------------------------------------------------

def test_bank_matches_onthefly_homogeneous():
    net = mlp(2, 3, hidden=(16, 16))
    stack = _stack(net, 4)
    w = [1.0, 2.0, 1.0, 1.0]
    src = _source()
    vx, vy = _val()
    off, i_off = feddf_fuse_stacked(net, stack, w, src,
                                    _fusion(logit_bank="off"), vx, vy,
                                    seed=3)
    on, i_on = feddf_fuse_stacked(net, stack, w, src,
                                  _fusion(logit_bank="on"), vx, vy, seed=3)
    assert i_on["logit_bank"] and not i_off["logit_bank"]
    assert i_on["steps"] == i_off["steps"]
    # identical sampled indices -> identical eval schedule and accuracies
    assert [s for s, _ in i_on["val_history"]] == \
        [s for s, _ in i_off["val_history"]]
    np.testing.assert_allclose([a for _, a in i_on["val_history"]],
                               [a for _, a in i_off["val_history"]],
                               atol=1e-6)
    _assert_trees_close(off, on)


def test_bank_matches_onthefly_swag():
    net = mlp(2, 3, hidden=(12,))
    stack = _stack(net, 3)
    w = [1.0, 1.0, 2.0]
    src = _source(seed=5)
    kw = dict(swag_samples=2, swag_scale=0.3)
    off, _ = feddf_fuse_stacked(net, stack, w, src,
                                _fusion(logit_bank="off", **kw), seed=7)
    on, info = feddf_fuse_stacked(net, stack, w, src,
                                  _fusion(logit_bank="on", **kw), seed=7)
    assert info["logit_bank"]
    _assert_trees_close(off, on)


def test_bank_matches_onthefly_heterogeneous_and_counts():
    """G=3 groups: equal trajectories AND >= G x fewer teacher forwards."""
    G = 3
    nets = [mlp(2, 3, hidden=(8,), name="s"),
            mlp(2, 3, hidden=(12,), name="m"),
            mlp(2, 3, hidden=(16,), name="l")]
    protos = [(nets[g], _stack(nets[g], 2, seed0=10 * g), [1.0, 1.0])
              for g in range(G)]
    src = _source(seed=9)

    TEACHER_FORWARDS.reset()
    f_off, i_off = feddf_fuse_heterogeneous_stacked(
        protos, src, _fusion(logit_bank="off"), seed=1)
    n_off = TEACHER_FORWARDS.count
    TEACHER_FORWARDS.reset()
    f_on, i_on = feddf_fuse_heterogeneous_stacked(
        protos, src, _fusion(logit_bank="on"), seed=1)
    n_on = TEACHER_FORWARDS.count

    for a, b in zip(f_off, f_on):
        _assert_trees_close(a, b)
    assert all(i["logit_bank"] for i in i_on)
    # the shared bank is built once: every student gathers, none forwards
    assert n_on > 0 and n_off >= G * n_on
    assert i_on[0]["teacher_batch_forwards"] == n_on
    assert all(i["teacher_batch_forwards"] == 0 for i in i_on[1:])
    assert all(i["teacher_batch_forwards"] > 0 for i in i_off)


def test_bank_build_cost_attributed_when_first_group_empty():
    """A round where prototype 0 has no clients must still charge the
    shared bank's build forwards to some fused group's info."""
    nets = [mlp(2, 3, hidden=(8,), name="a"), mlp(2, 3, hidden=(12,),
                                                  name="b")]
    protos = [(nets[0], None, []),
              (nets[1], _stack(nets[1], 2), [1.0, 1.0])]
    TEACHER_FORWARDS.reset()
    _, infos = feddf_fuse_heterogeneous_stacked(
        protos, _source(), _fusion(logit_bank="on"), seed=0)
    assert infos[0] == {"skipped": True}
    assert infos[1]["teacher_batch_forwards"] == TEACHER_FORWARDS.count > 0


def test_auto_uses_bank_with_pool_and_fallback_without():
    net = mlp(2, 3, hidden=(8,))
    stack = _stack(net, 2)
    tfn = make_teacher_logits_fn(net, stack)
    student = net.init(jax.random.PRNGKey(9))

    _, info = distill(net, student, [tfn], _source(), _fusion(), seed=0)
    assert info["logit_bank"] and info["bank_build_s"] > 0.0

    gen = GeneratorSource((2,))
    _, info = distill(net, student, [tfn], gen, _fusion(), seed=0)
    assert not info["logit_bank"]


def test_fused_kernel_bank_path_matches_reference():
    """ensemble_kl_pre wired into the scan == jnp reference loss path."""
    net = mlp(2, 3, hidden=(12,))
    stack = _stack(net, 3)
    src = _source(seed=11)
    w = [1.0, 1.0, 1.0]
    fus = dict(max_steps=25, patience=100, eval_every=25, batch_size=16,
               logit_bank="on")
    ref_p, _ = feddf_fuse_stacked(net, stack, w, src,
                                  FusionConfig(use_fused_kernel=False,
                                               **fus), seed=2)
    ker_p, _ = feddf_fuse_stacked(net, stack, w, src,
                                  FusionConfig(use_fused_kernel=True,
                                               **fus), seed=2)
    _assert_trees_close(ref_p, ker_p, atol=1e-4)


# ---------------------------------------------------------------------------
# bank construction + counter
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 1e-6),
                                       (jnp.bfloat16, 2e-2)])
def test_bank_rows_match_direct_forward(dtype, tol):
    net = mlp(2, 4, hidden=(16,))
    stack = _stack(net, 5)
    tfn = make_teacher_logits_fn(net, stack)
    pool = RNG.uniform(-2, 2, (130, 2)).astype(np.float32)  # odd N: padded
    bank = build_logit_bank([tfn], pool, chunk_size=64, dtype=dtype)
    assert bank.logits.dtype == dtype
    assert bank.logits.shape == (130, 4)
    assert bank.n == 130 and bank.n_teachers == 5
    assert bank.n_teacher_batch_forwards == 3 * 5  # ceil(130/64) chunks
    direct = jnp.mean(tfn(jnp.asarray(pool)).astype(jnp.float32), axis=0)
    np.testing.assert_allclose(np.asarray(bank.logits, dtype=np.float32),
                               np.asarray(direct), atol=tol, rtol=tol)


def test_forward_counter_tracks_build():
    net = mlp(2, 3, hidden=(8,))
    tfn = make_teacher_logits_fn(net, _stack(net, 4))
    TEACHER_FORWARDS.reset()
    build_logit_bank([tfn], RNG.uniform(-1, 1, (100, 2)).astype(np.float32),
                     chunk_size=50)
    assert TEACHER_FORWARDS.count == 2 * 4


def _direct_rows(tfns, pool, w=None):
    """The bank's rows computed straight from the teacher fns (fp32)."""
    t = jnp.concatenate([tfn(jnp.asarray(pool)) for tfn in tfns],
                        axis=0).astype(jnp.float32)
    if w is None:
        return jnp.mean(t, axis=0)
    return jnp.tensordot(jnp.asarray(w, jnp.float32) / np.sum(w), t,
                         axes=([0], [0]))


@pytest.mark.parametrize("case", ["homogeneous", "heterogeneous", "weighted",
                                  "int8"])
def test_bank_forward_compiles_once_across_rounds(case):
    """Fresh uploads of one shape every round: the stamped path passes the
    stacks as arguments, so three builds trace the bank forward once (the
    per-round re-compile this replaces traced it three times)."""
    from repro.core.logit_bank import BANK_COMPILES, dequantize_rows
    nets = [mlp(2, 4, hidden=(16,), name="a")]
    if case == "heterogeneous":
        nets.append(mlp(2, 4, hidden=(24,), name="b"))
    dtype = "int8" if case == "int8" else "float32"
    pool = RNG.uniform(-2, 2, (130, 2)).astype(np.float32)  # padded chunk
    BANK_COMPILES.reset()
    for rnd in range(3):
        tfns = [make_teacher_logits_fn(n, _stack(n, 3, seed0=10 * rnd + i))
                for i, n in enumerate(nets)]
        k = 3 * len(nets)
        w = (RNG.uniform(0.5, 2.0, k) if case == "weighted" else None)
        bank = build_logit_bank(tfns, pool, chunk_size=64, dtype=dtype,
                                teacher_weights=w)
        assert bank.n_teachers == k and bank.logits.shape == (130, 4)
        rows = dequantize_rows(bank.logits, bank.scales)
        tol = 0.05 if case == "int8" else 1e-5
        np.testing.assert_allclose(np.asarray(rows),
                                   np.asarray(_direct_rows(tfns, pool, w)),
                                   atol=tol, rtol=tol)
    assert BANK_COMPILES.count == 1, BANK_COMPILES.count


def test_plain_callable_bank_matches_stamped_rows():
    """A plain callable has no stack to pass, so it keeps the per-build
    closure (one trace per build) and yields the stamped path's rows."""
    from repro.core.logit_bank import BANK_COMPILES
    net = mlp(2, 4, hidden=(16,))
    stack = _stack(net, 4)
    tfn = make_teacher_logits_fn(net, stack)
    raw = lambda x: jax.vmap(  # noqa: E731 — deliberately attribute-less
        lambda p: net.apply(p, x, train=False))(stack)
    pool = RNG.uniform(-2, 2, (100, 2)).astype(np.float32)
    stamped = build_logit_bank([tfn], pool, chunk_size=50)
    BANK_COMPILES.reset()
    plain = [build_logit_bank([raw], pool, chunk_size=50) for _ in range(2)]
    assert BANK_COMPILES.count == 2
    for bank in plain:
        assert bank.n_teachers == 4
        np.testing.assert_allclose(np.asarray(bank.logits),
                                   np.asarray(stamped.logits),
                                   atol=1e-6, rtol=1e-6)


def _weight_constants(hlo: str, stack) -> list:
    """Lines of ``hlo`` that define a constant shaped like a weight leaf."""
    shapes = {"x".join(map(str, leaf.shape)) + "xf32"
              for leaf in jax.tree.leaves(stack)}
    return [line for line in hlo.splitlines()
            if "constant" in line
            and any(line.rstrip().endswith(f"tensor<{s}>") for s in shapes)]


def test_bank_program_carries_no_teacher_weights():
    """The stamped bank forward takes the weights as arguments: its
    lowered program holds no constant of a weight leaf's shape and does
    not grow with the width, where the closure path embeds them all."""
    from repro.core.logit_bank import _stacked_fwd
    xc = jnp.zeros((16, 2), jnp.float32)
    sizes = []
    for hidden in (64, 256):
        net = mlp(2, 3, hidden=(hidden,))
        stack = _stack(net, 4)
        hlo = _stacked_fwd([net], "float32", False).lower(
            (stack,), None, xc).as_text()
        assert _weight_constants(hlo, stack) == []
        sizes.append(len(hlo))
        tfn = make_teacher_logits_fn(net, stack)
        closure = jax.jit(lambda x: tfn(x)).lower(xc).as_text()
        assert _weight_constants(closure, stack)  # the check can see them
    # only the shapes' digits differ between the two widths
    assert abs(sizes[1] - sizes[0]) < 100, sizes


# ---------------------------------------------------------------------------
# source pool / index interface
# ---------------------------------------------------------------------------

def test_unlabeled_sample_equals_pool_gather():
    src = _source(n=64)
    key = jax.random.PRNGKey(4)
    idx = src.sample_indices(key, 16)
    np.testing.assert_array_equal(
        np.asarray(src.sample(key, 16)),
        np.asarray(jnp.asarray(src.pool())[idx]))


def test_generator_noise_have_no_pool_and_warn_when_forced():
    net = mlp(2, 3, hidden=(8,))
    tfn = make_teacher_logits_fn(net, _stack(net, 2))
    for src in (GeneratorSource((2,)), RandomNoiseSource((2,))):
        assert src.pool() is None
        assert bank_for_fusion([tfn], src, _fusion(logit_bank="auto")) \
            is None
        with pytest.warns(UserWarning, match="no indexable pool"):
            assert bank_for_fusion([tfn], src,
                                   _fusion(logit_bank="on")) is None


def test_hetero_pool_less_source_warns_once_per_fusion():
    """logit_bank='on' + generator source: ONE fallback warning at the
    fuse level, not one more per group-student."""
    import warnings as _w
    nets = [mlp(2, 3, hidden=(8,), name="a"),
            mlp(2, 3, hidden=(12,), name="b")]
    protos = [(n, _stack(n, 2, seed0=5 * i), [1.0, 1.0])
              for i, n in enumerate(nets)]
    with _w.catch_warnings(record=True) as caught:
        _w.simplefilter("always")
        feddf_fuse_heterogeneous_stacked(
            protos, GeneratorSource((2,)),
            _fusion(logit_bank="on", max_steps=25), seed=0)
    assert sum("no indexable pool" in str(w.message) for w in caught) == 1


def test_forward_count_handles_plain_callables():
    """Plain lambda teachers (no n_teachers attribute) still count their
    true K on the on-the-fly path — same ground truth as the builder."""
    net = mlp(2, 3, hidden=(8,))
    stack = _stack(net, 4)
    raw = lambda x: jax.vmap(  # noqa: E731 — deliberately attribute-less
        lambda p: net.apply(p, x, train=False))(stack)
    student = net.init(jax.random.PRNGKey(0))
    _, info = distill(net, student, [raw], GeneratorSource((2,)),
                      _fusion(logit_bank="off", max_steps=25), seed=0)
    assert info["teacher_batch_forwards"] == 25 * 4


def test_bank_mode_validated():
    net = mlp(2, 3, hidden=(8,))
    tfn = make_teacher_logits_fn(net, _stack(net, 2))
    with pytest.raises(ValueError, match="logit_bank"):
        bank_for_fusion([tfn], _source(), _fusion(logit_bank="maybe"))
    with pytest.raises(ValueError, match="bank_dtype"):
        bank_for_fusion([tfn], _source(), _fusion(bank_dtype="float64"))


# ---------------------------------------------------------------------------
# `auto` break-even heuristic (skip the build when the run is too short)
# ---------------------------------------------------------------------------

def test_expected_distill_steps():
    fus = _fusion(max_steps=10_000, patience=1_000, eval_every=100)
    # no validation -> no early stopping -> the full cap
    assert expected_distill_steps(fus, have_val=False) == 10_000
    # earliest plateau stop: first eval (always improves on the -1.0
    # initial best) + patience, on the eval_every grid
    assert expected_distill_steps(fus, have_val=True) == 1_100
    assert expected_distill_steps(
        _fusion(max_steps=10_000, patience=25, eval_every=100), True) == 200
    # patience >= max_steps -> the cap dominates
    assert expected_distill_steps(
        _fusion(max_steps=75, patience=1_000, eval_every=25), True) == 75


def test_auto_skips_bank_for_small_expected_runs():
    """auto + a patience that bounds the run below N/B rows: keep the
    on-the-fly path (the build would cost more forwards than it saves);
    'on' still insists."""
    net = mlp(2, 3, hidden=(8,))
    tfn = make_teacher_logits_fn(net, _stack(net, 2))
    src = _source(n=4000)
    vx, vy = _val()
    small = _fusion(max_steps=10_000, patience=25, eval_every=25,
                    batch_size=16)  # expected 50 steps * 16 << 4000
    bank, reason = resolve_bank(
        [tfn], src, small,
        expected_steps=expected_distill_steps(small, True))
    assert bank is None and reason == "skipped_small_run"

    student = net.init(jax.random.PRNGKey(3))
    _, info = distill(net, student, [tfn], src, small, vx, vy, seed=0)
    assert not info["logit_bank"]
    assert info["bank_decision"] == "skipped_small_run"

    # 'on' overrides the heuristic; long 'auto' runs still build
    on = _fusion(max_steps=50, patience=25, eval_every=25, batch_size=16,
                 logit_bank="on")
    _, info = distill(net, student, [tfn], src, on, vx, vy, seed=0)
    assert info["logit_bank"]
    PERSISTENT_BANK.clear()  # the 'on' build would otherwise be reused
    long_auto = _fusion(max_steps=200, patience=10_000, eval_every=25,
                        batch_size=32)  # 200 * 32 > 4000
    _, info = distill(net, student, [tfn], src, long_auto, vx, vy, seed=0)
    assert info["logit_bank"] and info["bank_decision"] == "bank"


def test_bank_decision_reaches_round_log():
    """The engine logs the per-round bank decision on RoundLog.bank."""
    from repro.core import FLConfig, run_federated
    from repro.data import (dirichlet_partition, gaussian_mixture,
                            train_val_test_split)
    ds = gaussian_mixture(1200, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, 6, 1.0, seed=0)
    net = mlp(2, 3, hidden=(16,))
    cfg = FLConfig(strategy="feddf", rounds=1, client_fraction=0.5,
                   local_epochs=2, local_batch_size=32, local_lr=0.05,
                   seed=0, fusion=FusionConfig(max_steps=50, patience=50,
                                               eval_every=25, batch_size=32,
                                               use_fused_kernel=False))
    res = run_federated(net, train, parts, val, test, cfg, source=_source())
    assert res.logs[0].bank in ("bank", "bank_reused")
    cfg_skip = dataclasses_replace_fusion(cfg, max_steps=10_000, patience=25,
                                          eval_every=25, batch_size=1)
    res = run_federated(net, train, parts, val, test, cfg_skip,
                        source=_source(n=4000))
    assert res.logs[0].bank == "skipped_small_run"


def dataclasses_replace_fusion(cfg, **kw):
    import dataclasses
    return dataclasses.replace(cfg, fusion=dataclasses.replace(cfg.fusion,
                                                               **kw))


# ---------------------------------------------------------------------------
# persistent bank for static teacher pools
# ---------------------------------------------------------------------------

def test_persistent_bank_reused_for_identical_teacher_stacks():
    """Fusing the exact same frozen teacher arrays again reuses the
    previous build's rows: zero teacher forwards, identical output."""
    net = mlp(2, 3, hidden=(16,))
    stack = _stack(net, 4)
    src = _source()
    vx, vy = _val()
    fus = _fusion(logit_bank="on")
    PERSISTENT_BANK.clear()
    try:
        TEACHER_FORWARDS.reset()
        p1, i1 = feddf_fuse_stacked(net, stack, [1.0] * 4, src, fus,
                                    vx, vy, seed=3)
        assert i1["bank_decision"] == "bank"
        assert TEACHER_FORWARDS.count > 0
        assert i1["teacher_batch_forwards"] == TEACHER_FORWARDS.count

        TEACHER_FORWARDS.reset()
        p2, i2 = feddf_fuse_stacked(net, stack, [1.0] * 4, src, fus,
                                    vx, vy, seed=3)
        assert i2["bank_decision"] == "bank_reused"
        assert TEACHER_FORWARDS.count == 0
        assert i2["teacher_batch_forwards"] == 0
        assert i2["bank_build_s"] == 0.0
        for a, b in zip(jax.tree.leaves(p1), jax.tree.leaves(p2)):
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    finally:
        PERSISTENT_BANK.clear()


def test_cached_bank_beats_small_run_skip():
    """A cached bank is free, so it is used even when the auto heuristic
    would have skipped a fresh BUILD."""
    net = mlp(2, 3, hidden=(8,))
    stack = _stack(net, 2)
    tfn = make_teacher_logits_fn(net, stack)
    src = _source(n=4000)
    small = _fusion(max_steps=10_000, patience=25, eval_every=25,
                    batch_size=16)  # expected 50 steps * 16 << 4000
    PERSISTENT_BANK.clear()
    try:
        exp = expected_distill_steps(small, True)
        bank, reason = resolve_bank([tfn], src, small, expected_steps=exp)
        assert bank is None and reason == "skipped_small_run"
        # build once (forced), then the same small-run resolve reuses it
        on = _fusion(logit_bank="on")
        assert resolve_bank([tfn], src, on)[1] == "built"
        bank, reason = resolve_bank([tfn], src, small, expected_steps=exp)
        assert bank is not None and reason == "reused"
    finally:
        PERSISTENT_BANK.clear()


def test_persistent_bank_drops_when_uploads_die():
    """The cache holds the keyed uploads WEAKLY: once a run's teacher
    stacks are GC'd, the entry (and its bank rows) goes with them —
    no process-lifetime pinning of a round's working set."""
    import gc
    net = mlp(2, 3, hidden=(8,))
    src = _source(n=64)
    fus = _fusion(logit_bank="on", max_steps=25)
    PERSISTENT_BANK.clear()
    try:
        stack = _stack(net, 2)
        feddf_fuse_stacked(net, stack, [1.0, 1.0], src, fus, seed=0)
        tfn = make_teacher_logits_fn(net, stack)
        assert resolve_bank([tfn], src, fus)[1] == "reused"
        del stack, tfn
        gc.collect()
        assert PERSISTENT_BANK._bank is None  # entry died with the uploads
    finally:
        PERSISTENT_BANK.clear()


def test_hetero_break_even_scales_with_group_count():
    """The shared bank amortizes over all G students: a run too short for
    ONE student can still justify the build for G of them."""
    G = 3
    nets = [mlp(2, 3, hidden=(8,), name=f"g{i}") for i in range(G)]
    protos = [(n, _stack(n, 2, seed0=11 * i), [1.0, 1.0])
              for i, n in enumerate(nets)]
    vx, vy = _val()
    # expected 75 steps * 32 = 2400 rows per student: below a 4000-row
    # pool alone, above it for G=3 students (7200) -> hetero builds
    fus = _fusion(max_steps=75, patience=1_000, eval_every=25,
                  batch_size=32)
    src = _source(n=4000)
    tfn = make_teacher_logits_fn(nets[0], protos[0][1])
    PERSISTENT_BANK.clear()
    try:
        assert resolve_bank(
            [tfn], src, fus,
            expected_steps=expected_distill_steps(fus, True)
        )[1] == "skipped_small_run"
        _, infos = feddf_fuse_heterogeneous_stacked(protos, src, fus,
                                                    vx, vy, seed=0)
        assert all(i["bank_decision"] == "bank" for i in infos)
    finally:
        PERSISTENT_BANK.clear()


def test_persistent_bank_invalidated_on_any_upload_change():
    net = mlp(2, 3, hidden=(16,))
    src = _source()
    fus = _fusion(logit_bank="on")
    PERSISTENT_BANK.clear()
    try:
        s1 = _stack(net, 3)
        feddf_fuse_stacked(net, s1, [1.0] * 3, src, fus, seed=1)
        TEACHER_FORWARDS.reset()
        s2 = _stack(net, 3, seed0=50)  # new uploads -> new leaf identities
        _, info = feddf_fuse_stacked(net, s2, [1.0] * 3, src, fus, seed=1)
        assert info["bank_decision"] == "bank"  # rebuilt, not reused
        assert TEACHER_FORWARDS.count > 0
    finally:
        PERSISTENT_BANK.clear()


def test_persistent_bank_shared_across_hetero_round_repeat():
    """Repeating a heterogeneous fusion with unchanged teacher stacks
    (feddf_init_from='previous'-style static teacher pools) rebuilds
    nothing; every group's info reports the reuse."""
    nets = [mlp(2, 3, hidden=(8,), name="a"),
            mlp(2, 3, hidden=(12,), name="b")]
    protos = [(n, _stack(n, 2, seed0=7 * i), [1.0, 1.0])
              for i, n in enumerate(nets)]
    src = _source(seed=3)
    fus = _fusion(logit_bank="on")
    PERSISTENT_BANK.clear()
    try:
        f1, i1 = feddf_fuse_heterogeneous_stacked(protos, src, fus, seed=2)
        assert all(i["bank_decision"] == "bank" for i in i1)
        TEACHER_FORWARDS.reset()
        f2, i2 = feddf_fuse_heterogeneous_stacked(protos, src, fus, seed=2)
        assert all(i["bank_decision"] == "bank_reused" for i in i2)
        assert TEACHER_FORWARDS.count == 0
        assert all(i["teacher_batch_forwards"] == 0 for i in i2)
        for a, b in zip(f1, f2):
            _assert_trees_close(a, b, atol=0)
    finally:
        PERSISTENT_BANK.clear()


# ---------------------------------------------------------------------------
# SWAG stacked helper
# ---------------------------------------------------------------------------

def test_swag_teachers_stacked_matches_list_path():
    net = mlp(2, 3, hidden=(10,))
    plist = [net.init(jax.random.PRNGKey(i)) for i in range(3)]
    legacy = tree_stack(swag_teachers(plist, 2, scale=0.4, seed=5))
    stacked = swag_teachers_stacked(tree_stack(plist), 2, scale=0.4, seed=5)
    for a, b in zip(jax.tree.leaves(legacy), jax.tree.leaves(stacked)):
        np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


# ---------------------------------------------------------------------------
# spec plumbing + kernel auto mode
# ---------------------------------------------------------------------------

def test_fusion_spec_roundtrips_and_validates_bank_fields():
    from repro.api import ExperimentSpec
    from repro.api.spec import FusionSpec

    spec = ExperimentSpec()
    spec.strategy.fusion = FusionSpec(logit_bank="on", bank_dtype="bfloat16",
                                      use_fused_kernel="auto")
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    spec.validate()

    for bad in (dict(logit_bank="sometimes"), dict(bank_dtype="fp16"),
                dict(use_fused_kernel="cpu"), dict(use_fused_kernel=1)):
        s = ExperimentSpec()
        s.strategy.fusion = FusionSpec(**bad)
        with pytest.raises(ValueError):
            s.validate()


def test_use_fused_kernel_auto_resolves_per_backend():
    from repro.kernels.ops import use_pallas
    assert use_pallas(True) is True
    assert use_pallas(False) is False
    assert use_pallas("auto") == (jax.default_backend() == "tpu")
    # bool("off") is True — unrecognized strings must fail loudly
    with pytest.raises(ValueError, match="use_fused_kernel"):
        use_pallas("off")


# ---------------------------------------------------------------------------
# sharded bank build on a multi-device mesh (forced host devices in a
# subprocess: the parent's jax is already initialised single-device)
# ---------------------------------------------------------------------------

def test_sharded_bank_matches_unsharded_on_4_device_mesh():
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {src!r})
import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.common.pytree import tree_stack
from repro.core import mlp
from repro.core.feddf import make_teacher_logits_fn
from repro.core.logit_bank import build_logit_bank
from repro.launch.mesh import make_client_mesh

assert len(jax.devices()) == 4, jax.devices()
net = mlp(4, 5, hidden=(16,))
stack = tree_stack([net.init(jax.random.PRNGKey(i)) for i in range(3)])
tfn = make_teacher_logits_fn(net, stack)
pool = np.random.default_rng(0).uniform(-3, 3, (512, 4)).astype(np.float32)

plain = build_logit_bank([tfn], pool)
mesh = make_client_mesh(4)
sharding = NamedSharding(mesh, P("data"))
sharded = build_logit_bank([tfn], pool, sharding=sharding)

# the sharded bank really lives on all 4 devices, rows split over them
assert len(sharded.logits.sharding.device_set) == 4, sharded.logits.sharding
assert len(sharded.pool.sharding.device_set) == 4, sharded.pool.sharding
# and holds exactly the unsharded rows
np.testing.assert_array_equal(np.asarray(sharded.logits),
                              np.asarray(plain.logits))
np.testing.assert_array_equal(np.asarray(sharded.pool),
                              np.asarray(plain.pool))
# a gather by sampled index (what the distill scan does) agrees too
idx = jax.random.randint(jax.random.PRNGKey(7), (64,), 0, 512)
np.testing.assert_array_equal(np.asarray(sharded.logits[idx]),
                              np.asarray(plain.logits[idx]))
print("SHARDED_BANK_OK", sharded.n_teacher_batch_forwards)
""".format(src=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.stdout.count("SHARDED_BANK_OK") == 1, r.stdout + r.stderr


def test_fused_distill_over_sharded_bank_matches_one_device():
    """A bank spread over a 4-device mesh runs the fused-kernel distill
    chunk replicated under shard_map (Mosaic kernels cannot be
    partitioned automatically); the student it distils equals the one
    distilled from the same bank on one device."""
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    code = """
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
import sys
sys.path.insert(0, {src!r})
import jax
import numpy as np
from jax.sharding import NamedSharding, PartitionSpec as P
from repro.common.pytree import tree_stack
from repro.core import mlp
from repro.core.feddf import FusionConfig, distill, make_teacher_logits_fn
from repro.core.logit_bank import build_logit_bank
from repro.data import UnlabeledDataset
from repro.launch.mesh import make_client_mesh

net = mlp(4, 5, hidden=(16,))
stack = tree_stack([net.init(jax.random.PRNGKey(i)) for i in range(3)])
tfn = make_teacher_logits_fn(net, stack)
pool = np.random.default_rng(0).uniform(-3, 3, (512, 4)).astype(np.float32)
source = UnlabeledDataset(pool)
fusion = FusionConfig(max_steps=40, patience=40, eval_every=20,
                      batch_size=32, use_fused_kernel=True)
student = net.init(jax.random.PRNGKey(9))
sharding = NamedSharding(make_client_mesh(4), P("data"))
outs = []
for bank in (build_logit_bank([tfn], pool),
             build_logit_bank([tfn], pool, sharding=sharding)):
    params, info = distill(net, student, [tfn], source, fusion, bank=bank)
    assert info["steps"] == 40, info
    outs.append(params)
leaf = jax.tree.leaves(outs[1])[0]
assert len(leaf.sharding.device_set) == 4, leaf.sharding
for a, b in zip(*map(jax.tree.leaves, outs)):
    np.testing.assert_allclose(np.asarray(a), np.asarray(b),
                               rtol=1e-6, atol=1e-7)
print("SHARDED_FUSED_OK")
""".format(src=os.path.join(root, "src"))
    r = subprocess.run([sys.executable, "-c", code], capture_output=True,
                       text=True)
    assert r.stdout.count("SHARDED_FUSED_OK") == 1, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# quantized banks (int8 / fp8_e4m3 rows + per-row fp32 scales)
# ---------------------------------------------------------------------------

def test_quantize_roundtrip_error_bound():
    from repro.core.logit_bank import dequantize_rows, quantize_rows
    rows = jnp.asarray(np.random.default_rng(2).normal(
        0, 4, (33, 17)).astype(np.float32))
    rows = rows.at[5].set(0.0)  # an all-zero row must round-trip exactly
    q, scales = quantize_rows(rows, "int8")
    assert q.dtype == jnp.int8
    assert scales.shape == (33,) and scales.dtype == jnp.float32
    deq = dequantize_rows(q, scales)
    # symmetric round-to-nearest: per-element error <= scale/2 per row
    err = np.abs(np.asarray(deq) - np.asarray(rows))
    assert (err <= np.asarray(scales)[:, None] * 0.5 + 1e-7).all()
    np.testing.assert_array_equal(np.asarray(deq[5]), 0.0)
    # each row's |amax| maps to +-127 exactly -> representable losslessly
    amax_err = np.abs(np.abs(np.asarray(deq)).max(1)
                      - np.abs(np.asarray(rows)).max(1))
    assert (amax_err <= np.asarray(scales) * 1e-5 + 1e-7).all()


def test_quantize_fp8_when_supported():
    from repro.core.logit_bank import dequantize_rows, quantize_rows
    if not hasattr(jnp, "float8_e4m3fn"):
        pytest.skip("this jax has no float8_e4m3fn")
    rows = jnp.asarray(np.random.default_rng(3).normal(
        0, 2, (9, 24)).astype(np.float32))
    q, scales = quantize_rows(rows, "fp8_e4m3")
    assert q.dtype == jnp.float8_e4m3fn
    deq = dequantize_rows(q, scales)
    # fp8 e4m3 keeps ~2 mantissa-ish digits: relative error per row
    err = np.abs(np.asarray(deq) - np.asarray(rows))
    assert (err <= np.asarray(scales)[:, None] * 448 * 0.0625 + 1e-6).all()


def test_quantized_bank_nbytes_and_metadata():
    from repro.core.logit_bank import dequantize_rows
    net = mlp(2, 4, hidden=(16,))
    tfn = make_teacher_logits_fn(net, _stack(net, 3))
    pool = RNG.uniform(-2, 2, (96, 2)).astype(np.float32)
    f32 = build_logit_bank([tfn], pool)
    q = build_logit_bank([tfn], pool, chunk_size=40, dtype="int8")
    assert not f32.quantized and f32.dtype_name == "float32"
    assert f32.scales is None and f32.nbytes == 96 * 4 * 4
    assert q.quantized and q.dtype_name == "int8"
    assert q.logits.dtype == jnp.int8 and q.scales.shape == (96,)
    # the ISSUE's memory claim: N x C x 1 bytes of rows + N x 4 of scales
    assert q.nbytes == 96 * 4 * 1 + 96 * 4
    assert f32.nbytes / q.nbytes >= 2.0  # C=4 is the worst case; C>=64 >3.5
    # chunked quantization == whole-bank quantization of the fp32 rows
    deq = dequantize_rows(q.logits, q.scales)
    err = np.abs(np.asarray(deq) - np.asarray(f32.logits, dtype=np.float32))
    assert (err <= np.asarray(q.scales)[:, None] * 0.5 + 1e-6).all()


def test_int8_bank_trajectory_tracks_fp32():
    """Distilling from the int8 bank (unfused dequantize-then-KL and the
    fused gather+dequantize kernel) stays within a tight tolerance of the
    fp32-bank trajectory, and the info stream reports dtype + bytes."""
    net = mlp(2, 3, hidden=(16, 16))
    stack = _stack(net, 4)
    src = _source()
    w = [1.0] * 4
    PERSISTENT_BANK.clear()
    try:
        f32_p, i_f32 = feddf_fuse_stacked(
            net, stack, w, src, _fusion(logit_bank="on"), seed=3)
        PERSISTENT_BANK.clear()
        q_p, i_q = feddf_fuse_stacked(
            net, stack, w, src,
            _fusion(logit_bank="on", bank_dtype="int8"), seed=3)
        PERSISTENT_BANK.clear()
        qf_p, i_qf = feddf_fuse_stacked(
            net, stack, w, src,
            _fusion(logit_bank="on", bank_dtype="int8",
                    use_fused_kernel=True), seed=3)
    finally:
        PERSISTENT_BANK.clear()
    assert i_f32["bank_dtype"] == "float32"
    assert i_q["bank_dtype"] == i_qf["bank_dtype"] == "int8"
    assert 0 < i_q["bank_nbytes"] < i_f32["bank_nbytes"]
    # the quantization perturbs teacher logits, not the rng stream: the
    # trajectory stays close to fp32 (measured ~3.5e-5 after 50 steps)
    _assert_trees_close(f32_p, q_p, atol=5e-3)
    _assert_trees_close(f32_p, qf_p, atol=5e-3)
    # fused vs unfused on the SAME int8 bank is kernel-tolerance tight
    _assert_trees_close(q_p, qf_p, atol=1e-4)


def test_round_log_carries_bank_dtype_and_nbytes():
    from repro.core import FLConfig, run_federated
    from repro.data import (dirichlet_partition, gaussian_mixture,
                            train_val_test_split)
    ds = gaussian_mixture(1200, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, 6, 1.0, seed=0)
    net = mlp(2, 3, hidden=(16,))
    cfg = FLConfig(strategy="feddf", rounds=1, client_fraction=0.5,
                   local_epochs=2, local_batch_size=32, local_lr=0.05,
                   seed=0, fusion=FusionConfig(max_steps=50, patience=50,
                                               eval_every=25, batch_size=32,
                                               use_fused_kernel=False,
                                               logit_bank="on",
                                               bank_dtype="int8"))
    res = run_federated(net, train, parts, val, test, cfg, source=_source())
    log = res.logs[0]
    assert log.bank in ("bank", "bank_reused")
    assert log.bank_dtype == "int8" and log.bank_nbytes > 0
    # old checkpoints (dicts without the new fields) still round-trip
    from repro.core.engine import RoundLog
    d = dataclasses_replace_roundlog_dict(log)
    old = RoundLog(**d)
    assert old.bank_dtype == "" and old.bank_nbytes == 0


def dataclasses_replace_roundlog_dict(log):
    import dataclasses
    d = dataclasses.asdict(log)
    d.pop("bank_dtype"), d.pop("bank_nbytes")
    return d


# ---------------------------------------------------------------------------
# distill-axis bucketing (per-group batch sizes -> padded capacities)
# ---------------------------------------------------------------------------

def _hetero_protos():
    nets = [mlp(2, 3, hidden=(8,), name="s"),
            mlp(2, 3, hidden=(12,), name="m"),
            mlp(2, 3, hidden=(16,), name="l")]
    return [(nets[g], _stack(nets[g], 2, seed0=10 * g), [1.0, 1.0])
            for g in range(3)]


def test_distill_bucketing_reduces_padding():
    """batch_sizes=(12,16,48): 'none' pads every group to 48 (68 wasted
    rows/step); 'pow2' gives the small students intermediate capacities."""
    protos = _hetero_protos()
    src = _source(seed=9)
    runs = {}
    for kind in ("none", "pow2"):
        fus = _fusion(logit_bank="on", max_steps=50,
                      batch_sizes=(12, 16, 48), distill_bucket=kind)
        runs[kind] = feddf_fuse_heterogeneous_stacked(protos, src, fus,
                                                      seed=1)
    i_none, i_pow2 = runs["none"][1], runs["pow2"][1]
    assert [i["batch_capacity"] for i in i_none] == [48, 48, 48]
    assert [i["padded_rows_per_step"] for i in i_none] == [36, 32, 0]
    assert [i["batch_capacity"] for i in i_pow2] == [16, 16, 48]
    assert [i["padded_rows_per_step"] for i in i_pow2] == [4, 0, 0]

    # trajectories agree across bucketings: bitwise where the padded
    # capacity matches, reassociation-level (XLA reduce order over the
    # different padded shapes) where it does not
    f_none, f_pow2 = runs["none"][0], runs["pow2"][0]
    for gi, (a, b) in enumerate(zip(f_none, f_pow2)):
        if i_none[gi]["batch_capacity"] == i_pow2[gi]["batch_capacity"]:
            _assert_trees_close(a, b, atol=0)
        else:
            _assert_trees_close(a, b, atol=1e-6)


def test_distill_batch_sizes_validated():
    protos = _hetero_protos()
    with pytest.raises(ValueError, match="batch_sizes"):
        feddf_fuse_heterogeneous_stacked(
            protos, _source(), _fusion(batch_sizes=(8, 16)), seed=0)


def test_fusion_spec_roundtrips_and_validates_distill_bucketing():
    from repro.api import ExperimentSpec
    from repro.api.spec import FusionSpec

    spec = ExperimentSpec()
    n_protos = len(spec.cohort.prototypes)
    spec.strategy.fusion = FusionSpec(bank_dtype="int8",
                                      batch_sizes=[32] * n_protos,
                                      distill_bucket="pow2",
                                      distill_max_buckets=2)
    assert ExperimentSpec.from_json(spec.to_json()) == spec
    spec.validate()
    # fp8_e4m3 is always a VALID spec literal (runtime gates jax support)
    spec.strategy.fusion = FusionSpec(bank_dtype="fp8_e4m3")
    spec.validate()

    for bad in (dict(bank_dtype="int4"), dict(distill_bucket="pow3"),
                dict(distill_max_buckets=0),
                dict(batch_sizes=[32] * (n_protos + 1)),
                dict(batch_sizes=[0] * n_protos)):
        s = ExperimentSpec()
        s.strategy.fusion = FusionSpec(**bad)
        with pytest.raises(ValueError):
            s.validate()
