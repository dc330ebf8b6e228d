"""Share of the traced window in which no operation ran on the device:
1 - (union of the XLA operations' intervals) / (window), averaged over
the chips."""
import tracefile


def read(ctx):
    if not ctx["trace"]["devices"]:
        return None
    return 100.0 * (1.0 - tracefile.busy_s(ctx["trace"]) / ctx["window_s"])
