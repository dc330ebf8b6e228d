"""Device milliseconds per round of the logit-bank build
(``core/logit_bank.py:build_logit_bank``), from the profiler trace."""
from _common import module_ms_per_round


def read(ctx):
    return module_ms_per_round(ctx, "bank_build_ms")
