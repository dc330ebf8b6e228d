"""The trace reduction, on a small trace recorded on a TPU v5e (the fused
logit-bank kernel's loss and gradient, a 2048x2048 matmul and two small
jitted programs) and on hand-made intervals."""
import json
import os
import sys

import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(BENCH, "metrics"))

import tracefile  # noqa: E402

KERNEL = 'custom_call_target="tpu_custom_call"'


@pytest.fixture(scope="module")
def tr():
    with open(os.path.join(BENCH, "tests", "data", "v5e_trace.json")) as f:
        return json.load(f)


def test_union_merges_overlaps():
    assert tracefile.union([(5, 7), (0, 2), (1, 3), (7, 8)]) == \
        [(0.0, 3.0), (5.0, 8.0)]


def test_busy_and_idle(tr):
    ops = tr["devices"]["0"]["ops"]
    busy = tracefile.busy_s(tr)
    assert 0 < busy <= sum(o[3] - o[2] for o in ops) / 1e9
    assert busy == pytest.approx(634966e-9, rel=1e-6)
    span = (max(o[3] for o in ops) - min(o[2] for o in ops)) / 1e9
    assert busy < span


def test_module_time(tr):
    assert tracefile.module_s(tr, ["jit_counted"]) == \
        pytest.approx(342754e-9, rel=1e-6)
    both = tracefile.module_s(tr, ["jit_counted", "jit_fwd"])
    assert both > tracefile.module_s(tr, ["jit_counted"])
    assert tracefile.module_s(tr, ["jit_not_there"]) is None


def test_kernel_time(tr):
    s, n = tracefile.ops_s(tr, "jit__lambda", KERNEL)
    assert n == 6  # three loss-and-gradient calls: forward + backward each
    assert s == pytest.approx(213434e-9, rel=1e-6)
    assert tracefile.ops_s(tr, "jit_chunk", KERNEL) == (None, 0)


def test_ops_are_tagged_with_their_module(tr):
    for mod, name, a, b in tr["devices"]["0"]["ops"]:
        owners = [m for m in tr["devices"]["0"]["modules"]
                  if m[1] <= a <= m[2]]
        assert [m[0] for m in owners] == [mod]


def test_idle_gaps_named_by_host_span():
    op = lambda a, b: ["m", "%fusion.1 = f32[8] fusion(f32[8] %x)", a, b]
    tr = {"devices": {"0": {"modules": [], "ops": [
        op(0, 10), op(50, 60), op(65, 70)]}},
          "host": [["train_clients", 0, 100], ["build_round_batches", 12, 48]]}
    gaps = tracefile.idle_gaps(tr)
    assert gaps[0] == ["build_round_batches", pytest.approx(40e-9)]
    assert gaps[1] == ["train_clients", pytest.approx(5e-9)]


def test_breakdown_of_recorded_trace(tr):
    b = tracefile.breakdown(tr)
    assert 0 < len(b["device_ops"]) <= 10 and len(b["idle_gaps"]) <= 10
    assert b["device_ops"][0][1] >= b["device_ops"][-1][1]
    assert all(isinstance(n, str) and s >= 0 for n, s in b["device_ops"])


def test_readers_leave_out_what_is_absent(tr):
    import importlib.util

    def reader(name):
        spec = importlib.util.spec_from_file_location(
            name, os.path.join(BENCH, "metrics", f"{name}.py"))
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read

    ctx = {"trace": tr, "rounds": 1, "window_s": 1.0, "round_s": 1.0,
           "chips": 1, "spans": [], "compiles": 0,
           "flops": {"client": 1e9}, "traffic": {}, "models": [{}],
           "peaks": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9}}
    assert reader("bank_build_ms")(ctx) is not None  # jit_fwd ran
    assert reader("distill_ms")(ctx) is None
    assert reader("ensemble_kl_bank_roofline")(ctx) is None
    assert reader("host_batches_ms")(ctx) is None
    assert reader("client_train_ms")(ctx) == pytest.approx(0.342754)
    share = reader("device_idle_share")(ctx)
    assert 0 < share <= 100
    assert reader("collective_ms")(ctx) is None  # one chip, no collective


def test_collective_ms_takes_the_busiest_chip():
    import collective_ms
    op = lambda text, a, b: ["jit_counted", text, a, b]
    gather = "%all-gather.3 = f32[16,4]{1,0} all-gather(f32[4,4]{1,0} %p)"
    start = "%all-reduce-start.1 = f32[] all-reduce-start(f32[] %x)"
    done = "%all-reduce-done.1 = f32[] all-reduce-done(f32[] " \
        "%all-reduce-start.1)"
    reads = "%fusion.7 = f32[16] fusion(f32[16,4]{1,0} %all-gather.3)"
    tr = {"devices": {
        "0": {"modules": [], "ops": [op(gather, 0, 4e6), op(reads, 4e6, 9e6),
                                     op(start, 10e6, 11e6),
                                     op(done, 10.5e6, 13e6)]},
        "1": {"modules": [], "ops": [op(gather, 0, 2e6)]}}, "host": []}
    # chip 0: 4 ms + the union of 10..11 and 10.5..13 ms, over two rounds
    assert collective_ms.read({"trace": tr, "rounds": 2}) == \
        pytest.approx(3.5)
