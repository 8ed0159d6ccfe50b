"""The round step's share of the chip's peak over the traced stretch, for
the hybrid LM: model FLOPs of its whole segments
(``chipbench/flops_hybrid.py``, from the configuration's keys) over the
stretch's length on the device's clock, over the chips' bf16 peak (the
rate at which the model's default-precision float32 matmuls run)."""
from chipbench import flops_hybrid


def read(ctx):
    if ctx["peaks"] is None or not ctx["segments"] or ctx["window_s"] <= 0:
        return None
    f = flops_hybrid.model_flops_per_cell_round(ctx["cfg"], ctx["traffic"],
                                                ctx["n_val"])
    work = f * ctx["segments"] * ctx["seg"] * ctx["cells"]
    return 100.0 * work / (ctx["window_s"] * ctx["chips"]
                           * ctx["peaks"]["flops_bf16"])
