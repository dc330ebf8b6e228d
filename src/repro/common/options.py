"""Shared option-literal sets for fusion knobs.

Single source of truth consumed by the runtime resolvers
(``core/logit_bank.py``, ``kernels/ops.py``) AND by the jax-free spec
validation (``api/spec.py``) — one place to extend when a new bank dtype
or kernel mode lands, so the two layers cannot drift.  Keep this module
dependency-free: spec.py must stay importable without jax.
"""
from __future__ import annotations

LOGIT_BANK_MODES = ("auto", "on", "off")
# float32 keeps bank trajectories bitwise-identical to on-the-fly; bfloat16
# halves the rows; int8 / fp8_e4m3 store quantized rows plus one fp32 scale
# per row (~4x smaller, dequantized inside the fused kernel)
BANK_DTYPES = ("float32", "bfloat16", "int8", "fp8_e4m3")
# the subset of BANK_DTYPES stored as (quantized rows, per-row fp32 scale)
QUANTIZED_BANK_DTYPES = ("int8", "fp8_e4m3")
FUSED_KERNEL_MODES = (True, False, "auto")

# capacity bucketing of heterogeneous FedDF's distillation batch sizes
# (core/client.py:bucket_capacities, docs/bucketing.md)
BUCKET_KINDS = ("none", "pow2", "quantile")

# client arrival processes of the population traffic model
# (population/traffic.py, docs/population.md)
ARRIVAL_KINDS = ("always", "bernoulli")

# fault-injection / defense knobs (population/faults.py, docs/robustness.md)
# "auto" activates a defense exactly when any injection rate is > 0, which
# keeps fault-free configs bit-identical to historic trajectories
SCREEN_MODES = ("auto", "on", "off")
BYZANTINE_MODES = ("sign_flip", "scale")

# transports of the distributed runtime (repro.dist, docs/distributed.md):
# "loopback" runs the client pods as in-process threads over queue pairs
# (deterministic, CI-testable); "tcp" spawns one OS process per client pod
# connected over localhost sockets.  Wire-codec names live in the codec
# registry (repro.dist.frames.available_codecs), not here, so a new codec
# registers in exactly one place.
TRANSPORT_KINDS = ("loopback", "tcp")
