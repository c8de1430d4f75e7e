"""``tp_collective_share``: the share of busy device time that collective
operations take (all-reduce, all-gather, reduce-scatter,
collective-permute, all-to-all, their start and done halves each by its
own events), per chip over the union of its operations' intervals in the
traced window, the mean over the chips (``harness.lm.collective_share``).
Finds nothing to read, and returns nothing, where the observation
carries no such share. Moves ``train_images_per_s``.
"""


def read(obs):
    share = getattr(obs, "collective_share", None)
    return None if share is None else 100.0 * share
