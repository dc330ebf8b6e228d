"""Host milliseconds per round building the clients' batch tensors: the
flight recorder's ``build_round_batches`` span (pure numpy, so its host
time is its time)."""
from _common import span_ms_per_round


def read(ctx):
    return span_ms_per_round(ctx, "build_round_batches")
