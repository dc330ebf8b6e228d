"""The comparison that decides ``correct``, at a size a test run holds.

A whole run of the harness (without its look for a chip) on a tiny
configuration comes out correct; with the timed path broken underneath
it does not, once per fault a one-chip cell can have; and the bfloat16
control, put in the program's place, fails the limits too."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)

import calibrate  # noqa: E402
import compare  # noqa: E402
import run  # noqa: E402

DATA = os.path.join(BENCH, "tests", "data")
SEED = 2 ** 31 + 4242


def _load(name):
    with open(os.path.join(DATA, name)) as f:
        return json.load(f)


def _run(config="tiny_config.json", strategy="feddf", fault=None,
         monkeypatch=None):
    config, traffic = _load(config), _load("tiny_feddf.json")
    traffic["strategy"] = strategy
    with open(os.path.join(os.path.dirname(BENCH), "BENCHMARK.json")) as f:
        manifest = json.load(f)
    if fault is not None:
        # a planted fault may rebind the program's module attributes:
        # restore them after the test
        for mod, name, value in calibrate.patched():
            monkeypatch.setattr(mod, name, value)
        build = run.build_engine

        def broken(*a, **kw):
            engine, proto = build(*a, **kw)
            calibrate.plant(engine, fault)
            return engine, proto
        monkeypatch.setattr(run, "build_engine", broken)
    cell = {"name": "tiny", "config": "tiny", "traffic": "tiny", "chips": 1}
    return run.run_cell(cell, config, traffic, manifest, seed=SEED,
                        seconds=0.1, trace=False, require_chip=False,
                        cache_dir=None, log=lambda s: None)


@pytest.mark.parametrize("config,strategy", [
    ("tiny_config.json", "feddf"),
    ("tiny_config.json", "fedavg"),
    ("tiny_ladder_config.json", "feddf")])
def test_sound_run_is_correct(config, strategy):
    out = _run(config, strategy)
    assert out["correct"], out["checks"]
    assert list(out)[-2] == "checks"  # the last key printed
    assert out["attempted"] >= 1 and out["failed"] == 0
    assert set(out["metrics"]) == {"round_s", "peak_hbm_gib", "setup_s"}
    for v in out["checks"].values():
        assert set(v) == {"value", "limit"}


@pytest.mark.parametrize("fault", ["unchanged", "half_batch", "bank_row",
                                   "accuracy", "distill_unchanged",
                                   "kl_scale"])
def test_broken_run_is_not_correct(fault, monkeypatch):
    out = _run(fault=fault, monkeypatch=monkeypatch)
    assert not out["correct"], out["checks"]


@pytest.mark.parametrize("config", ["tiny_config.json",
                                    "tiny_ladder_config.json"])
def test_bfloat16_control_is_not_correct(config):
    config, traffic = _load(config), _load("tiny_feddf.json")
    models = run.model_dicts(config)
    limits = compare.load_limits()
    seed = SEED % run.SEED_SPAN
    inp = run.inputs_mod.make_inputs(SEED, models[0], traffic, len(models))
    proto = [k % len(models) for k in range(len(inp.parts))]
    ref = calibrate.reference(models, traffic, inp, proto, seed, "float32")
    ctl = calibrate.reference(models, traffic, inp, proto, seed, "bfloat16")
    assert compare.judge(compare.numbers(ref, ref), limits)
    assert not compare.judge(compare.numbers(ctl, ref), limits)
