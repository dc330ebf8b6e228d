"""Share of the chips' bf16 peak that client training reaches while it
runs: model FLOPs of the real (unmasked) local steps in the window, over
the device time of the client-update program times chips times peak."""
import tracefile
from _common import data


def read(ctx):
    s = tracefile.module_s(ctx["trace"], data("client_train_mfu")["modules"])
    if not s:
        return None
    return 100.0 * ctx["flops"]["client"] / (
        s * ctx["chips"] * ctx["peaks"]["flops_bf16"])
