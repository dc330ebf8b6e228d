"""The FLOP and byte formulas against what XLA and the kernel see."""
import os
import sys

import jax
import jax.extend
import jax.numpy as jnp
import numpy as np
import pytest

BENCH = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, BENCH)
sys.path.insert(0, os.path.join(os.path.dirname(BENCH), "src"))

import flops  # noqa: E402
import run  # noqa: E402

KIND = run.load_kind("tiny_transformer")

MODEL = {"d_model": 256, "n_layers": 2, "n_heads": 4, "vocab_size": 512,
         "seq_len": 64, "n_classes": 4}
BATCH = 8


def _net():
    from repro.core.nets import tiny_transformer
    m = MODEL
    net = tiny_transformer(m["vocab_size"], m["n_classes"], m["seq_len"],
                           d_model=m["d_model"], n_layers=m["n_layers"],
                           n_heads=m["n_heads"])
    params = net.init(jax.random.PRNGKey(0))
    x = jnp.zeros((BATCH, m["seq_len"]), jnp.int32)
    return net, params, x


def _xla_flops(fn, *args):
    cost = jax.jit(fn).lower(*args).compile().cost_analysis()
    cost = cost[0] if isinstance(cost, (list, tuple)) else cost
    return float(cost["flops"])


def test_parameter_count_matches_the_program():
    _, params, _ = _net()
    n = sum(int(np.prod(a.shape)) for a in jax.tree.leaves(params))
    emb = MODEL["vocab_size"] * MODEL["d_model"] \
        + MODEL["seq_len"] * MODEL["d_model"]
    assert KIND.non_embedding_params(MODEL) == n - emb


def test_forward_flops_against_xla():
    net, params, x = _net()
    xla = _xla_flops(lambda p, x: net.apply(p, x, train=False), params, x)
    ours = KIND.forward_flops_per_token(MODEL) * BATCH * MODEL["seq_len"]
    # XLA also counts the elementwise work (norms, softmax, GELU)
    assert ours <= xla <= 1.06 * ours


def test_train_flops_against_xla():
    net, params, x = _net()
    y = jnp.zeros((BATCH,), jnp.int32)

    def loss(p, x, y):
        logits = net.apply(p, x)
        return -jnp.mean(jax.nn.log_softmax(logits)[jnp.arange(BATCH), y])

    xla = _xla_flops(jax.grad(loss), params, x, y)
    ours = flops.train_flops_per_token(KIND, MODEL) * BATCH * MODEL["seq_len"]
    assert 0.97 * ours <= xla <= 1.10 * ours


@pytest.mark.parametrize("dtype,itemsize", [(jnp.float32, 4),
                                            (jnp.bfloat16, 2)])
def test_bank_kernel_bytes_against_its_operands(dtype, itemsize):
    """Bytes of the arrays the forward and backward Pallas calls take and
    give, with the bank counted as the B rows its index map reads."""
    from repro.kernels.ensemble_kl import ensemble_kl_bank
    b, n, c = 16, 64, 4
    s = jnp.zeros((b, c), jnp.float32)
    bank = jnp.zeros((n, c), dtype)
    idx = jnp.zeros((b,), jnp.int32)
    scale = jnp.ones((b,), jnp.float32)
    jaxpr = jax.make_jaxpr(jax.grad(
        lambda s: ensemble_kl_bank(s, bank, scale, idx, 1.0, True)))(s)
    calls = []

    def walk(jx):
        for eqn in jx.eqns:
            if eqn.primitive.name == "pallas_call":
                calls.append(eqn)
                continue
            for v in eqn.params.values():
                for sub in (v if isinstance(v, (list, tuple)) else [v]):
                    if isinstance(sub, jax.extend.core.ClosedJaxpr):
                        walk(sub.jaxpr)
                    elif isinstance(sub, jax.extend.core.Jaxpr):
                        walk(sub)

    walk(jaxpr.jaxpr)
    assert len(calls) == 2
    total = 0
    for eqn in calls:
        for v in list(eqn.invars) + list(eqn.outvars):
            shape = tuple(v.aval.shape)
            rows = b if shape and shape[0] == n else (
                int(np.prod(shape[:1])) if shape else 1)
            total += rows * int(np.prod(shape[1:])) * v.aval.dtype.itemsize
    _, ours = flops.kl_bank_cost(b, n, c, itemsize)
    assert ours == total
