"""§Perf variant levers: lowering coverage + numeric equivalence.

The optimized step-builder options (constrain_acts, chunked attention,
dp_heavy/dp_heavy_z3 layouts, microbatching) must (a) lower+compile on a
debug mesh for representative reduced architectures and (b) compute the
same mathematics as the baseline (microbatch accumulation == single batch).
"""
import os
import subprocess
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

VARIANT_LOWER_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax
from repro import configs
from repro.common.arch_config import reduced
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(2, 4)
shape = dataclasses.replace(configs.get_shape("train_4k"), seq_len=32,
                            global_batch=8)
pshape = dataclasses.replace(configs.get_shape("prefill_32k"), seq_len=64,
                             global_batch=8)

# every §Perf lever x a representative arch (dense w/ SWA, MoE, hybrid)
for arch, kw, shp in [
    ("gemma3-4b", dict(constrain_acts=True), shape),
    ("minicpm-2b", dict(constrain_acts=True, layout="dp_heavy"), shape),
    ("phi3-medium-14b", dict(constrain_acts=True, layout="dp_heavy_z3"),
     shape),
    ("qwen3-8b", dict(constrain_acts=True, microbatch=2), shape),
    ("granite-moe-1b-a400m", dict(constrain_acts=True), pshape),
    ("zamba2-1.2b", dict(constrain_acts=True), shape),
]:
    cfg = dataclasses.replace(reduced(configs.get(arch)),
                              attn_impl="chunked", attn_chunk=16)
    bundle = steps_mod.make_step(cfg, shp, mesh, fsdp=True, **kw)
    compiled = bundle.lower(mesh).compile()
    assert compiled.cost_analysis() is not None
    print("LOWER_OK", arch)

# distill step with constraints (the §Perf-C configuration)
cfg = reduced(configs.get("gemma3-4b"))
bundle = steps_mod.make_distill_step(cfg, mesh, n_teachers=2, batch_size=8,
                                     seq_len=16, constrain_acts=True)
bundle.lower(mesh).compile()
print("LOWER_OK distill")
"""

MICROBATCH_EQUIV_SNIPPET = r"""
import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
import dataclasses
import jax, jax.numpy as jnp
import numpy as np
from repro import configs
from repro.common.arch_config import reduced
from repro.launch import steps as steps_mod
from repro.launch.mesh import make_debug_mesh

mesh = make_debug_mesh(2, 4)
shape = dataclasses.replace(configs.get_shape("train_4k"), seq_len=16,
                            global_batch=8)
cfg = reduced(configs.get("qwen3-8b"))

def materialize(tree, seed=0):
    leaves, treedef = jax.tree.flatten(tree)
    rng = np.random.default_rng(seed)
    out = []
    for s in leaves:
        if jnp.issubdtype(s.dtype, jnp.integer):
            out.append(jnp.asarray(rng.integers(0, 7, s.shape), s.dtype))
        else:
            out.append(jnp.asarray(0.02 * rng.normal(size=s.shape), s.dtype))
    return jax.tree.unflatten(treedef, out)

results = {}
for mb in (1, 2):
    b = steps_mod.make_step(cfg, shape, mesh, fsdp=True, microbatch=mb,
                            constrain_acts=True, param_dtype=jnp.float32)
    args = materialize(b.args)
    with mesh:
        fn = jax.jit(b.fn, in_shardings=b.in_shardings,
                     out_shardings=b.out_shardings)
        params, opt_state, step, metrics = fn(*args)
    results[mb] = (jax.tree.leaves(params)[0], metrics["loss"])

p1, l1 = results[1]
p2, l2 = results[2]
np.testing.assert_allclose(float(l1), float(l2), rtol=1e-5)
np.testing.assert_allclose(np.asarray(p1), np.asarray(p2), atol=1e-5)
print("MICROBATCH_EQUIV_OK")
"""


def _run(snippet):
    return subprocess.run(
        [sys.executable, "-c", snippet], capture_output=True, text=True,
        timeout=900, env={**os.environ, "PYTHONPATH": "src"}, cwd=ROOT)


def test_perf_variant_steps_lower():
    res = _run(VARIANT_LOWER_SNIPPET)
    assert res.stdout.count("LOWER_OK") == 7, res.stdout + res.stderr


def test_microbatch_accumulation_matches_single_batch():
    res = _run(MICROBATCH_EQUIV_SNIPPET)
    assert "MICROBATCH_EQUIV_OK" in res.stdout, res.stdout + res.stderr
