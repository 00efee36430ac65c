"""Model FLOP utilization of FL training: the CNN's forward and backward
FLOPs times the samples trained (padded steps not counted), over the
traced window times the chip's bf16 peak, in percent."""
from bench.flops import cnn_train_flops_per_sample


def read(ctx):
    samples = ctx["counters"].get("samples_trained")
    if not samples:
        return None
    flops = samples * cnn_train_flops_per_sample(ctx["config"]["model"])
    return flops / (ctx["window_s"] * ctx["peaks"]["bf16_flops"]) * 100
