"""Host milliseconds per round of ``evaluate_round``: the fused globals'
test and validation accuracies, which end in host floats, so the span
waits for the device."""
from _common import span_ms_per_round


def read(ctx):
    return span_ms_per_round(ctx, "evaluate_round")
