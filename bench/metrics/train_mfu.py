"""``train_mfu``: the whole training step's share of the chip's bf16 peak.

Required matmul work per step (``harness.flops.step_flops``: forward convs
and head, dW and dX at the kept output channels, nothing recomputed),
times the steps of the traced window, over the window's length, over the
peak. Moves ``train_images_per_s``.
"""


def read(obs):
    red = obs.reduction
    if obs.steps < 1 or red.window_s <= 0:
        return None
    return 100.0 * obs.step_flops * obs.steps / red.window_s / obs.peaks.bf16_flops
