"""``device_idle_share``: the share of the traced window in which no
operation ran on the device (1 - union of the device operations'
intervals over the window). Moves ``train_images_per_s``.
"""


def read(obs):
    red = obs.reduction
    if red.window_s <= 0:
        return None
    return 100.0 * (1.0 - red.busy_s / red.window_s)
