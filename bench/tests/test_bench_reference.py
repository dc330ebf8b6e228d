"""The reference's device memory: at every local and distillation step of
round 1 it holds one model's training state, beside the pool, the bank
rows and the evaluation rows, however many clients the round has."""
import gc
import json
import math
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import reference  # noqa: E402
import run  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
SEED = 2 ** 31 + 5151


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _live_bytes():
    import jax
    return sum(a.nbytes for a in jax.live_arrays())


def _replay(n_active):
    """Round 1 of the tiny cell with ``n_active`` of its 6 clients: (the
    largest live device bytes above those held before the replay, over
    every step call, and as the replay reports them; a model's bytes; the
    data's bytes; the replay)."""
    import jax
    config, traffic = _load("tiny_config.json"), _load("tiny_feddf.json")
    traffic["client_fraction"] = n_active / traffic["n_clients"]
    kind = run.config_kind(config)
    models = run.model_dicts(config, kind)
    inp = run.inputs_mod.make_inputs(SEED, models[0], traffic, 1)
    proto = [0] * len(inp.parts)
    m = reference.Model(models[0], traffic, kind)
    seen = []

    def counted(step):
        def call(*a):
            seen.append(_live_bytes())
            return step(*a)
        return call
    m.client_step = counted(m.client_step)
    m.distill_step = counted(m.distill_step)
    model = sum(a.nbytes for a in jax.tree.leaves(m.init(0)))
    data = (inp.pool.nbytes + len(inp.pool) * models[0]["n_classes"] * 4
            + inp.val.x.nbytes + inp.val.y.nbytes + inp.test.x.nbytes
            + inp.test.y.nbytes)
    gc.collect()
    base = _live_bytes()
    ref = reference.round_one([m], traffic, inp, proto, SEED % run.SEED_SPAN,
                              False)
    assert len(seen) > 0 and ref.distilled == [True]
    return max(seen) - base, ref.live_bytes_max - base, model, data, ref


@pytest.fixture(scope="module")
def replays():
    return {k: _replay(k) for k in (2, 6)}


@pytest.mark.parametrize("n_active", [2, 6])
def test_replay_holds_one_models_training_state(replays, n_active):
    held, reported, model, data, ref = replays[n_active]
    assert len(ref.clients[0]) == n_active
    assert 0 < held <= 5 * model + data
    assert 0 < reported <= 5 * model + data


def test_replay_memory_does_not_grow_with_the_cohort(replays):
    assert replays[2][:2] == replays[6][:2]


@pytest.mark.parametrize("state_bytes,ahead", [(6_547_833_096, False),
                                               (796_591_320, True)])
def test_a_step_runs_ahead_only_where_memory_is_plentiful(
        monkeypatch, state_bytes, ahead):
    class Chip:  # one TPU v5e's memory, as JAX reports it
        def memory_stats(self):
            return {"bytes_limit": 16_909_336_064}
    monkeypatch.setattr(reference.jax, "devices", lambda: [Chip()])
    assert reference.Steps(state_bytes).ahead is ahead


def test_waiting_on_each_step_replays_the_same(replays, monkeypatch):
    init = reference.Steps.__init__

    def waiting(self, state_bytes):
        init(self, state_bytes)
        self.ahead = False
    monkeypatch.setattr(reference.Steps, "__init__", waiting)
    held, reported, _, _, ref = _replay(2)
    assert (held, reported) == replays[2][:2]
    for f in ("clients", "first_grad", "fused", "chunks", "test_acc",
              "val_acc", "pre_acc", "val_history"):
        assert getattr(ref, f) == getattr(replays[2][4], f), f
    assert (ref.bank == replays[2][4].bank).all()


def test_moonlight_sized_stand_in_is_one_chip_share():
    """The fixture the replay is proved on at size on the chip: one
    client of about Moonlight-16B-A3B's chip share, 545.6M parameters
    (2.18e9 B in float32), 4 of 8 clients active."""
    import jax
    import jax.numpy as jnp
    config = _load("moonlight_sized_config.json")
    traffic = _load("moonlight_sized_traffic.json")
    kind = run.config_kind(config, os.path.join(DATA, "kinds"))
    (model,) = run.model_dicts(config, kind)
    shapes = jax.eval_shape(
        lambda k: kind.init_params(k, model, jnp.float32),
        jax.random.PRNGKey(0))
    n = sum(math.prod(a.shape) for a in jax.tree.leaves(shapes))
    assert 5.45e8 < n < 5.47e8
    assert round(traffic["client_fraction"] * traffic["n_clients"]) == 4
