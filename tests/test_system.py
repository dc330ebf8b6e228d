"""End-to-end behaviour tests for the paper's system.

The headline claims, at CPU scale:
 1. FedDF's distillation step improves over its own FedAvg initialisation.
 2. The server pipeline (sample -> local train -> drop-worst -> fuse ->
    early-stop) runs end to end for every strategy.
 3. The sharded production step builders lower on a small mesh (subprocess
    with forced host devices, so this process stays single-device).
"""
import os
import subprocess
import sys

import numpy as np
import pytest

from repro.core import FLConfig, FusionConfig, mlp, run_federated
from repro.data import (UnlabeledDataset, dirichlet_partition,
                        gaussian_mixture, train_val_test_split)

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(scope="module")
def problem():
    ds = gaussian_mixture(3000, n_classes=3, dim=2, seed=0)
    train, val, test = train_val_test_split(ds)
    parts = dirichlet_partition(train.y, n_clients=8, alpha=0.1, seed=0)
    src = UnlabeledDataset(np.random.default_rng(1).uniform(
        -3, 3, (1500, 2)).astype(np.float32))
    return train, val, test, parts, src


@pytest.mark.parametrize("strategy", ["fedavg", "fedprox", "fedavgm",
                                      "feddf"])
def test_every_strategy_runs_and_learns(problem, strategy):
    train, val, test, parts, src = problem
    cfg = FLConfig(strategy=strategy, rounds=4, client_fraction=0.5,
                   local_epochs=10, local_batch_size=32, local_lr=0.05,
                   seed=0, fusion=FusionConfig(max_steps=200, patience=100,
                                               eval_every=50, batch_size=64))
    net = mlp(2, 3, hidden=(32, 32))
    res = run_federated(net, train, parts, val, test, cfg,
                        source=src if strategy == "feddf" else None)
    assert len(res.logs) == 4
    assert res.best_acc > 0.55  # well above 1/3 chance


def test_feddf_improves_over_its_own_init(problem):
    """The paper's core mechanism: post-distillation accuracy >= the
    weighted-average initialisation, per round (allowing small noise)."""
    train, val, test, parts, src = problem
    cfg = FLConfig(strategy="feddf", rounds=4, client_fraction=0.5,
                   local_epochs=15, local_batch_size=32, local_lr=0.05,
                   seed=0, fusion=FusionConfig(max_steps=300, patience=150,
                                               eval_every=50, batch_size=64))
    net = mlp(2, 3, hidden=(32, 32))
    res = run_federated(net, train, parts, val, test, cfg, source=src)
    gains = [l.test_acc - l.pre_distill_acc for l in res.logs]
    assert np.mean(gains) > -0.01, f"distillation hurt on average: {gains}"
    assert max(gains) > 0.0, "distillation never helped"


LOWER_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import jax
from repro import configs
from repro.common.arch_config import reduced
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_debug_mesh
import dataclasses

mesh = make_debug_mesh(2, 4)
shape = dataclasses.replace(configs.get_shape("train_4k"), seq_len=32,
                            global_batch=4)
for arch in ("qwen3-8b", "granite-moe-1b-a400m", "zamba2-1.2b"):
    cfg = reduced(configs.get(arch))
    bundle = steps_mod.make_step(cfg, shape, mesh, fsdp=True, remat=True)
    compiled = bundle.lower(mesh).compile()
    assert compiled.cost_analysis() is not None
    print("LOWER_OK", arch)
ds = dataclasses.replace(configs.get_shape("decode_32k"), seq_len=64,
                         global_batch=4)
cfg = reduced(configs.get("gemma3-4b"))
bundle = steps_mod.make_step(cfg, ds, mesh, fsdp=True)
compiled = bundle.lower(mesh).compile()
print("LOWER_OK decode")
"""


def test_step_builders_lower_on_mesh():
    res = subprocess.run(
        [sys.executable, "-c", LOWER_SNIPPET], capture_output=True,
        text=True, timeout=600, env={**os.environ, "PYTHONPATH": "src"},
        cwd=ROOT)
    assert res.stdout.count("LOWER_OK") == 4, res.stdout + res.stderr


def test_train_driver_cli_smoke(tmp_path):
    res = subprocess.run(
        [sys.executable, "-m", "repro.launch.train", "--strategy", "feddf",
         "--rounds", "2", "--clients", "4", "-C", "1.0", "--alpha", "1.0",
         "--local-epochs", "3", "--n-samples", "800", "--distill-steps",
         "100", "--out", str(tmp_path)],
        capture_output=True, text=True, timeout=900,
        env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)
    assert res.returncode == 0, res.stdout + res.stderr
    assert (tmp_path / "summary.json").exists()
