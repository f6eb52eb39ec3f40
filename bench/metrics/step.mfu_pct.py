"""Model FLOPs utilisation of the training step, per cent: the model's
FLOPs per step (forward and backward, no recompute; ``bench/work.py``)
times the steps of the traced window, over the window's length and the
chips' bf16 peak."""


def read(run):
    t = run.trace
    return 100.0 * run.work["flops"] * run.calls / t.window_s / (
        run.peak["bf16_flops_per_s"] * run.chips)
