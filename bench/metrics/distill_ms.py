"""Device milliseconds per round of distillation (``core/feddf.py:
distill``): its compiled chunks and the validation checks between them,
from the profiler trace."""
from _common import module_ms_per_round


def read(ctx):
    return module_ms_per_round(ctx, "distill_ms")
