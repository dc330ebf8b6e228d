"""Multi-host round driver: sync semantics, client axis sharded over a
device/host mesh.

Two entry points at two scales:

* :class:`MultiHostDriver` — the experiment path.  Attaches a 1-D client
  mesh (``launch/mesh.py:make_client_mesh``) to the
  :class:`~repro.core.engine.RoundEngine` so the K active clients of the
  batched client update train data-parallel across devices
  (``shard_map``).  Homogeneous runs require K to divide the device
  count; heterogeneous runs pad their run-fixed per-prototype client
  capacities up to mesh divisibility instead (padded slots carry
  all-False step masks, run no step and are sliced off),
  so skewed hetero cohorts shard too — see docs/bucketing.md.  Runs on
  real accelerators or a
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` simulated host
  mesh.  Round semantics are exactly the sync driver's.

* :func:`drive_fed_rounds` — the production-scale path.
  ``launch/steps.py:make_fed_round_step`` lowers one federated round's
  client phase (K transformer clients' local-SGD scans, client axis
  sharded over the mesh's data axes) but historically had NO driver loop.
  This is that loop: compile the step once, then per round broadcast the
  global model to the stacked client axis, run the local-SGD step on the
  mesh, and FedAvg the uploads back into the global.
"""
from __future__ import annotations

from typing import List, Optional, Tuple

from repro.drivers.base import register_driver
from repro.drivers.sync import SyncDriver


@register_driver("multihost")
class MultiHostDriver(SyncDriver):
    """Sync driver over a client-sharded mesh.  Heterogeneous engines
    pad their run-fixed per-prototype client capacities up to mesh
    divisibility (``RoundEngine.attach_mesh``), so they shard exactly
    like homogeneous cohorts."""

    def __init__(self, staleness: int = 0, prefetch: int = 1, mesh=None):
        super().__init__(staleness=staleness, prefetch=prefetch)
        self._mesh = mesh

    def run(self, engine, **kw):
        if engine.mesh is None:
            from repro.launch.mesh import make_client_mesh
            mesh = self._mesh if self._mesh is not None else \
                make_client_mesh()
            engine.attach_mesh(mesh, client_axis=engine.client_axis)
        return super().run(engine, **kw)


def drive_fed_rounds(cfg, mesh, *, rounds: int = 2, n_clients: int = 4,
                     local_steps: int = 2, batch_size: int = 2,
                     seq_len: int = 32, lr: float = 3e-4, seed: int = 0,
                     vocab: Optional[int] = None, param_dtype=None
                     ) -> Tuple[dict, List[dict]]:
    """Driver loop for the production fed-round step on a mesh.

    ``cfg`` is an :class:`~repro.common.arch_config.ArchConfig`; the step
    is compiled ONCE and reused every round.  Returns ``(final global
    params, per-round stats)`` where each stats dict records the round's
    global-update L2 norm (the convergence signal a coordinator would
    ship to monitoring).
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.launch.steps import make_fed_round_step
    from repro.models import transformer as T

    if param_dtype is None:
        param_dtype = jnp.float32
    bundle = make_fed_round_step(cfg, mesh, n_clients=n_clients,
                                 local_steps=local_steps,
                                 batch_size=batch_size, seq_len=seq_len,
                                 lr=lr, param_dtype=param_dtype)
    step = bundle.jit()  # compiled once, reused every round
    params = T.init(cfg, jax.random.PRNGKey(seed), param_dtype)
    v = vocab if vocab is not None else cfg.vocab_size
    rng = np.random.default_rng(seed)
    stats: List[dict] = []
    with mesh:
        for t in range(1, rounds + 1):
            # broadcast the global to the stacked client axis ([K, ...])
            # and place it on the mesh per the step's specs; the step
            # donates this buffer, so a fresh stack is materialised per
            # round (exactly the coordinator's per-round model push)
            stacked = jax.device_put(
                jax.tree.map(
                    lambda p: jnp.broadcast_to(p[None],
                                               (n_clients,) + p.shape),
                    params),
                bundle.in_shardings[0])
            toks = rng.integers(
                0, v, (n_clients, local_steps, batch_size, seq_len),
                dtype=np.int32)
            batch = jax.device_put(
                {"tokens": jnp.asarray(toks), "labels": jnp.asarray(toks)},
                bundle.in_shardings[1])
            new_stack = step(stacked, batch)
            new_params = jax.tree.map(
                lambda s: jnp.mean(s.astype(jnp.float32), axis=0
                                   ).astype(s.dtype), new_stack)
            delta = sum(
                float(jnp.sum((jnp.asarray(a, jnp.float32)
                               - jnp.asarray(b, jnp.float32)) ** 2))
                for a, b in zip(jax.tree.leaves(new_params),
                                jax.tree.leaves(params))) ** 0.5
            params = new_params
            stats.append({"round": t, "update_norm": delta})
    return params, stats
