"""Share of its roofline that the fused logit-bank kernel
(``kernels/ensemble_kl.py:ensemble_kl_bank``, forward and backward)
reaches: per forward+backward pair the least time the chip could take,
the larger of its FLOPs over peak and its bytes over HBM bandwidth
(``flops.kl_bank_cost``), summed over the pairs in the window, over the
kernels' device time.  The kernels are the Pallas calls in the
distillation chunk."""
import flops
import tracefile
from _common import data


def read(ctx):
    d = data("ensemble_kl_bank_roofline")
    s, n = tracefile.ops_s(ctx["trace"], d["module"], d["contains"])
    if not s or n < 2:
        return None
    job, m = ctx["traffic"], ctx["models"][0]
    itemsize = {"float32": 4, "bfloat16": 2}.get(job["bank_dtype"], 1)
    f, b = flops.kl_bank_cost(int(job["distill_batch"]), int(job["pool"]),
                              int(m["n_classes"]), itemsize)
    pk = ctx["peaks"]
    t_min = max(f / pk["flops_bf16"], b / pk["hbm_bytes_per_s"])
    return 100.0 * (n / 2) * t_min / s
