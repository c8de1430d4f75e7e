"""``sparse_bwd_kernel_roofline``: the Pallas sparse-backward kernels'
share of their roofline.

The least time the chip could take for the kept-channel backward
contractions of one step (per contraction the larger of its FLOPs over
the bf16 peak and its least bytes over the HBM peak, summed;
``harness.flops.sparse_kernel_calls``), times the steps of the traced
window, over the summed device time of the kernels' trace events. Finds
nothing to read, and returns nothing, where no such kernel ran. Moves
``train_images_per_s``.
"""


def read(obs):
    kernel_s = obs.reduction.kernel_s
    if kernel_s <= 0 or obs.kernel_least_s <= 0:
        return None
    return 100.0 * obs.kernel_least_s * obs.steps / kernel_s
