"""Device milliseconds per round of the batched client update
(``core/client.py:make_batched_local_update``), from the profiler trace."""
from _common import module_ms_per_round


def read(ctx):
    return module_ms_per_round(ctx, "client_train_ms")
