"""Whole-step model FLOP utilization, in %: the model's training FLOPs per
sample (three times the forward convolution and dense FLOPs) times the
client samples of the traced rounds, over the traced window on the
device's clock (the ``fed.window`` annotation in the profiler's trace),
over the chips' bf16 peak.  Convolutions on float32 inputs run as one bf16
MXU pass by default on the TPU, so the bf16 peak is the denominator."""


def read(run):
    if run.summary is None or not run.rounds:
        return None
    flops = run.train_flops_per_sample * run.samples
    return 100.0 * flops / run.summary.window_s / (run.chips * run.peaks["bf16_flops_per_s"])
