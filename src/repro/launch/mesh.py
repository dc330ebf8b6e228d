"""Production mesh construction.

A FUNCTION (not a module-level constant) so importing this module never
touches jax device state.  Target hardware: TPU v5e — 256 chips per pod in a
16x16 2D arrangement; the multi-pod mesh adds a leading "pod" axis over the
data-center network.
"""
from __future__ import annotations

import dataclasses

import jax
from jax.sharding import AxisType


@dataclasses.dataclass(frozen=True)
class DevicePeaks:
    """Published per-chip peaks used by the roofline analyses."""

    flops_bf16: float     # FLOP/s
    hbm_bw: float         # bytes/s
    ici_bw: float         # bytes/s per interconnect link


#: Keyed by ``jax.Device.device_kind``.  TPU v5e: Google Cloud
#: documentation, "TPU v5e" — 197 TFLOP/s bf16, 819 GB/s HBM, 1,600 Gbit/s
#: chip-to-chip interconnect over 4 links (50 GB/s per link).
PEAKS = {
    "TPU v5 lite": DevicePeaks(flops_bf16=197e12, hbm_bw=819e9,
                               ici_bw=50e9),
}

#: The device the analytic rooflines (``launch/dryrun.py``,
#: ``benchmarks/distill_bench.py``) are computed for.
TARGET_DEVICE_KIND = "TPU v5 lite"


def device_peaks(kind: str) -> DevicePeaks:
    """Peaks of a ``device_kind``; a kind not in :data:`PEAKS` is an error,
    never a default."""
    if kind not in PEAKS:
        raise ValueError(f"no published peaks for device kind {kind!r}; "
                         f"known kinds: {sorted(PEAKS)}")
    return PEAKS[kind]


def _mesh(shape, axes) -> jax.sharding.Mesh:
    # Auto axes: the engine's sharding constraints and gathers are written
    # for compiler-propagated shardings, not explicit sharding types
    return jax.make_mesh(shape, axes,
                         axis_types=(AxisType.Auto,) * len(shape))


def make_production_mesh(*, multi_pod: bool = False) -> jax.sharding.Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _mesh(shape, axes)


def make_debug_mesh(data: int = 2, model: int = 2) -> jax.sharding.Mesh:
    """Small mesh for CPU integration tests (requires
    xla_force_host_platform_device_count >= data*model)."""
    return _mesh((data, model), ("data", "model"))


def make_host_mesh(hosts: int | None = None,
                   model: int = 1) -> jax.sharding.Mesh:
    """("data", "model") mesh for the multi-host fed-round driver
    (``repro.drivers.multihost.drive_fed_rounds``): each "data" slice
    holds whole client replicas (clients shard over it), "model" is the
    within-client tensor-parallel width.  Defaults to every visible
    device on the data axis — on a simulated mesh set
    ``XLA_FLAGS=--xla_force_host_platform_device_count=N`` first."""
    hosts = hosts or len(jax.devices()) // model
    return _mesh((hosts, model), ("data", "model"))


def make_client_mesh(n: int | None = None) -> jax.sharding.Mesh:
    """1-D ("data",) mesh for the federated round engine: the stacked
    client axis of ``make_batched_local_update`` shards over it, so K
    active clients train data-parallel.  Homogeneous runs need K to
    divide ``n``; heterogeneous runs pad their client
    capacities up to divisibility (docs/bucketing.md).  Defaults to every
    visible device."""
    n = n or len(jax.devices())
    return _mesh((n,), ("data",))
