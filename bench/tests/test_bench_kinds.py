"""Model kinds (``bench/models/<kind>.py``) and cells on several chips.

The tiny transformer reads through its kind exactly what the harness read
before kinds existed (golden values recorded from that harness on the
CPU, ``data/parent_round_one.json``); a second kind, kept with the tests
(``data/kinds``), runs through a whole run with no harness file edited
for it; the reference's client loss adds a kind's auxiliary term; an
unknown kind fails with the kinds found; and a four-chip cell, on four
forced host devices, shards its client stacks and is correct, and is not
correct with the exchange between the chips left out."""
import hashlib
import json
import math
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import flops  # noqa: E402
import reference  # noqa: E402
import run  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
KINDS = os.path.join(DATA, "kinds")
SEED = 2 ** 31 + 4242
CONFIGS = ["tiny_config.json", "tiny_ladder_config.json"]
ROUND_ONE = ("clients", "first_grad", "fused", "chunks", "distilled",
             "test_acc", "val_acc", "pre_acc", "ens_acc", "val_history")


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _manifest():
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        return json.load(f)


def _setup(config_name, models_dir=run.MODELS, strategy="feddf"):
    config, traffic = _load(config_name), _load("tiny_feddf.json")
    traffic["strategy"] = strategy
    kind = run.config_kind(config, models_dir)
    models = run.model_dicts(config, kind)
    inp = run.inputs_mod.make_inputs(SEED, models[0], traffic, len(models))
    proto = [k % len(models) for k in range(len(inp.parts))]
    return config, traffic, kind, models, inp, proto


@pytest.fixture(scope="module")
def parent():
    return _load("parent_round_one.json")


@pytest.mark.parametrize("config", CONFIGS)
def test_tiny_transformer_model_dicts_match_the_parent(config, parent):
    assert _setup(config)[3] == parent[config]["model_dicts"]


@pytest.mark.parametrize("config", CONFIGS)
def test_tiny_transformer_round_flops_match_the_parent(config, parent):
    _, traffic, kind, models, inp, proto = _setup(config)
    cohorts = flops.cohorts(SEED % run.SEED_SPAN, len(inp.parts),
                            float(traffic["client_fraction"]), 2)
    got = [flops.round_flops(kind, lambda p: models[p], traffic, inp, proto,
                             a) for a in cohorts]
    assert got == parent[config]["round_flops"]


@pytest.mark.parametrize("config", CONFIGS)
def test_tiny_transformer_reference_matches_the_parent(config, parent):
    _, traffic, kind, models, inp, proto = _setup(config)
    ref = reference.round_one(
        [reference.Model(m, traffic, kind) for m in models], traffic, inp,
        proto, SEED % run.SEED_SPAN, len(models) > 1)
    want = parent[config]["reference"]
    assert hashlib.sha256(np.ascontiguousarray(
        ref.bank, np.float32).tobytes()).hexdigest() == want["bank_sha256"]
    for k in ROUND_ONE:
        assert json.loads(json.dumps(getattr(ref, k))) == want[k], k


# -- a second kind, with its program net registered by the test ---------------

def _narrow_net(task, d_model, d_ff, n_layers, n_heads, name=None):
    """The program side of ``kinds/narrow_ffn.py``: a pre-norm encoder
    whose feed-forward is ``d_ff`` wide; weights drawn by the kind's rule."""
    import jax
    import jax.numpy as jnp
    from repro.core.nets import Net

    kw = task.model_kwargs
    model = {"d_model": d_model, "d_ff": d_ff, "n_layers": n_layers,
             "vocab_size": kw["vocab"], "seq_len": kw["seq_len"],
             "n_classes": kw["n_classes"]}
    kind = run.load_kind("narrow_ffn", KINDS)
    hd = d_model // n_heads

    def norm(w, x):
        return x / jnp.sqrt(jnp.mean(jnp.square(x), -1, keepdims=True)
                            + 1e-6) * w

    def apply(params, x, train=True):
        b, s = x.shape
        h = jnp.take(params["embed"], x, axis=0) + params["pos"][:s]
        for l in range(n_layers):
            p = params[f"layer_{l}"]
            qkv = (norm(p["ln1"], h) @ p["wqkv"]).reshape(b, s, 3, n_heads,
                                                           hd)
            q, k, v = qkv[:, :, 0], qkv[:, :, 1], qkv[:, :, 2]
            w = jax.nn.softmax(jnp.einsum("bqhd,bkhd->bhqk", q, k)
                               * (1.0 / math.sqrt(hd)), axis=-1)
            h = h + jnp.einsum("bhqk,bkhd->bqhd", w, v).reshape(
                b, s, d_model) @ p["wo"]
            h = h + jax.nn.gelu(norm(p["ln2"], h) @ p["w1"]) @ p["w2"]
        return h.mean(axis=1) @ params["head"]["w"] + params["head"]["b"]

    return Net(init=lambda key: kind.init_params(key, model, jnp.float32),
               apply=apply, apply_with_stats=lambda p, x: (apply(p, x), p),
               name=name or "narrow")


@pytest.fixture(scope="module")
def narrow():
    from repro.api.registries import register_model
    register_model("bench_test_narrow_ffn")(_narrow_net)
    return "narrow_ffn_config.json"


def test_second_kind_builds_its_own_net(narrow):
    config, traffic, kind, models, inp, proto = _setup(narrow, KINDS)
    engine, _ = run.build_engine(config, traffic, inp, SEED % run.SEED_SPAN,
                                 kind)
    import jax
    params = engine.nets[0].init(jax.random.PRNGKey(0))
    assert params["layer_0"]["w1"].shape == (32, 64)  # 2 x dim
    with pytest.raises(ValueError, match="4 x dim"):
        run.model_dicts(config, run.load_kind("tiny_transformer"))


def test_second_kind_run_is_correct(narrow):
    config, traffic = _load(narrow), _load("tiny_feddf.json")
    cell = {"name": "narrow", "config": "narrow", "traffic": "tiny",
            "chips": 1}
    out = run.run_cell(cell, config, traffic, _manifest(), seed=SEED,
                       seconds=0.1, trace=False, require_chip=False,
                       cache_dir=None, log=lambda s: None, models_dir=KINDS)
    assert out["correct"], out["checks"]
    assert out["attempted"] >= 1 and out["failed"] == 0


def test_second_kind_counts_its_own_flops(narrow):
    _, traffic, kind, models, inp, proto = _setup(narrow, KINDS)
    tiny = dict(models[0])
    del tiny["d_ff"]
    active = flops.cohorts(SEED % run.SEED_SPAN, len(inp.parts),
                           float(traffic["client_fraction"]), 1)[0]
    got = flops.round_flops(kind, lambda p: models[p], traffic, inp, proto,
                            active)
    wide = flops.round_flops(run.load_kind("tiny_transformer"),
                             lambda p: tiny, traffic, inp, proto, active)
    d, s = 32, 16
    # per token and layer: the feed-forward's 2 x (2 x d x 2d) FLOPs
    # forward, not 2 x (2 x d x 4d)
    fewer = 2 * 2 * d * (4 * d - 2 * d) * 2  # two layers
    assert wide["bank"] - got["bank"] == \
        pytest.approx(len(inp.pool) * s * fewer * len(active))


def test_client_loss_adds_the_kinds_auxiliary_term():
    _, traffic, kind, models, inp, proto = _setup(
        "aux_decay_config.json", KINDS, strategy="fedavg")
    seed = SEED % run.SEED_SPAN
    m = reference.Model(models[0], traffic, kind)
    ref = reference.round_one([m], traffic, inp, proto, seed, False)
    decay = np.asarray(m.init(seed)["decay"], np.float64)
    # the logits never read `decay`: its gradient is the auxiliary term's
    assert ref.first_grad["['decay']"] == pytest.approx(
        kind.LAMBDA * np.linalg.norm(decay), rel=1e-5)
    assert ref.first_grad["['head']['w']"] > 0


def test_unknown_kind_lists_the_kinds(monkeypatch):
    with pytest.raises(ValueError, match="tiny_transformer"):
        run.load_kind("no_such_kind")

    def no_work(*a, **kw):
        raise AssertionError("inputs made for an unknown kind")
    monkeypatch.setattr(run.inputs_mod, "make_inputs", no_work)
    config = dict(_load("tiny_config.json"), model="no_such_kind")
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    with pytest.raises(ValueError) as e:
        run.run_cell(cell, config, _load("tiny_feddf.json"), _manifest(),
                     seed=SEED, seconds=0.1, trace=False,
                     require_chip=False, cache_dir=None, models_dir=KINDS)
    assert "'no_such_kind'" in str(e.value)
    assert "aux_decay" in str(e.value) and "narrow_ffn" in str(e.value)


@pytest.fixture(scope="module")
def four_chip_runs():
    """A sound run of a tiny four-chip cell, and one with the exchange
    between the chips left out, in one process on four forced host
    devices: (result, log lines) of each."""
    code = textwrap.dedent(f"""
        import json, os, sys
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
        sys.path.insert(0, {BENCH!r})
        import jax
        import calibrate, run
        assert len(jax.devices()) == 4
        data = {DATA!r}
        load = lambda n: json.load(open(os.path.join(data, n)))
        with open(os.path.join(os.path.dirname(run.BENCH),
                               "BENCHMARK.json")) as f:
            manifest = json.load(f)
        cell = {{"name": "tiny.x4", "config": "tiny", "traffic": "tiny_x4",
                 "chips": 4}}
        build, res = run.build_engine, {{}}
        for fault in (None, "exchange"):
            def broken(*a, **kw):
                engine, proto = build(*a, **kw)
                if fault is not None:
                    calibrate.plant(engine, fault)
                return engine, proto
            run.build_engine = broken
            lines = []
            out = run.run_cell(cell, load("tiny_config.json"),
                               load("tiny_feddf_x4.json"), manifest,
                               seed={SEED}, seconds=0.1, trace=False,
                               require_chip=False, cache_dir=None,
                               log=lines.append)
            out.pop("_check_lines")
            res[str(fault)] = {{"out": out, "lines": lines}}
        print(json.dumps(res))
    """)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    r = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert r.returncode == 0, r.stderr[-4000:]
    return json.loads(r.stdout.strip().splitlines()[-1])


def test_four_chip_cell_shards_client_stacks_and_is_correct(four_chip_runs):
    out, lines = four_chip_runs["None"]["out"], four_chip_runs["None"]["lines"]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert "client stacks: group 0: 4 clients over devices [0, 1, 2, 3], " \
        "1 per device" in lines
    window = [l for l in lines if l.startswith("window:")]
    assert len(window) == 1 and "compiled or loaded=0," in window[0]


def test_four_chip_cell_without_exchange_is_not_correct(four_chip_runs):
    out = four_chip_runs["exchange"]["out"]
    assert not out["correct"], out["checks"]
    checks = out["checks"]
    assert checks["bank_logits"]["value"] > checks["bank_logits"]["limit"]


def test_run_logs_what_the_replay_held(four_chip_runs):
    lines = four_chip_runs["None"]["lines"]
    held = [l for l in lines if l.startswith("reference: live_bytes_max=")]
    assert len(held) == 1
    n = int(held[0].split("=")[1].split()[0])
    assert n > 0 and "peak_bytes_in_use=" in held[0]
