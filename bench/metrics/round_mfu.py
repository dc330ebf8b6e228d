"""The whole round's share of the chips' bf16 peak: model FLOPs of the
window's rounds (client steps, bank, distillation, evaluations; real
work only, each counted once) over the traced window's seconds times
chips times peak.  Float32 matmuls at default precision run as one bf16
pass on the TPU, so the bf16 peak is the one they are held to."""


def read(ctx):
    total = sum(ctx["flops"].values())
    if total <= 0:
        return None
    return 100.0 * total / (ctx["round_s"] * ctx["rounds"] * ctx["chips"]
                            * ctx["peaks"]["flops_bf16"])
