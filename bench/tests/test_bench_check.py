"""The comparison that decides ``correct``, at a size a test run holds.

A whole run of the harness (without its look for a chip) on a tiny
configuration comes out correct; with the timed path broken underneath
it does not, once per fault a one-chip cell can have; and the bfloat16
control, put in the program's place, fails the limits too."""
import dataclasses
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
    kind = run.config_kind(config)
    models = run.model_dicts(config, kind)
    limits = compare.load_limits()
    seed = SEED % run.SEED_SPAN
    inp = run.inputs_mod.make_inputs(SEED, models[0], traffic, len(models))
    proto = [k % len(models) for k in range(len(inp.parts))]
    ref = calibrate.reference(models, traffic, inp, proto, seed, "float32",
                              kind)
    ctl = calibrate.reference(models, traffic, inp, proto, seed, "bfloat16",
                              kind)
    assert compare.judge(compare.numbers(ref, ref), limits)
    assert not compare.judge(compare.numbers(ctl, ref), limits)


def _chunk_round(first_grad, chunks):
    """A round 1 that holds nothing but its distillations' first chunks."""
    import reference
    n = len(chunks)
    return reference.RoundOne(
        clients=[[]], first_grad=first_grad, bank=None, fused=[{}] * n,
        chunks=chunks, distilled=[True] * n, test_acc=[1.0] * n,
        val_acc=[1.0] * n, pre_acc=[1.0] * n, ens_acc=None)


def _recorded(seed):
    d = _load("x4_distill_chunks.json")["seeds"][seed]
    return (_chunk_round(d["first_grad"], d["program"]),
            _chunk_round(d["first_grad"], d["reference"]))


@pytest.mark.parametrize("seed,left_out,change,grad", [
    ("224676372", 1, None, None),
    ("2147505001", 0, 0.02992850475501689, 0.051168223259474774)])
def test_saturated_distillation_is_left_out(seed, left_out, change, grad):
    # recorded on the chip: a distillation whose every reference gradient
    # leaf is under a thousandth of the clients' median leaf moves its
    # student by round-off (the program's change a third of the
    # reference's on most leaves) and is left out; an ordinary one reads
    # what it read before the rule
    prog, ref = _recorded(seed)
    nums = compare.numbers(prog, ref)
    assert nums.get("distill_change") == change
    assert nums.get("distill_grad") == grad
    assert compare.judge(nums, compare.load_limits())
    assert compare.not_compared(prog, ref)["distill_left_out"] == left_out


def test_left_out_distillation_does_not_hide_the_others():
    # two students, one saturated and one ordinary (as a ladder has them):
    # a fault in the ordinary one's chunk still fails the distill numbers
    sat_p, sat_r = _recorded("224676372")
    ord_p, ord_r = _recorded("2147505001")
    first = ord_r.first_grad
    still = {"change": {k: 0.0 for k in ord_p.chunks[0]["change"]},
             "grad": {k: 0.0 for k in ord_p.chunks[0]["grad"]}}
    ref = _chunk_round(first, [sat_r.chunks[0], ord_r.chunks[0]])
    sound = _chunk_round(first, [sat_p.chunks[0], ord_p.chunks[0]])
    broken = _chunk_round(first, [sat_p.chunks[0], still])
    limits = compare.load_limits()
    assert compare.judge(compare.numbers(sound, ref), limits)
    nums = compare.numbers(broken, ref)
    assert nums["distill_change"] > 0.9 and not compare.judge(nums, limits)
    assert compare.not_compared(broken, ref)["distill_left_out"] == 1
    # and a program that runs no distillation at all is never left out
    none = dataclasses.replace(sound, chunks=[])
    assert compare.numbers(none, ref)["distill_change"] == float("inf")
