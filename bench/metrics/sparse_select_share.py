"""``sparse_select_share``: the share of the backward's device time that
ssProp's selection takes.

The traced window's backward work under the ``ssprop_select`` scope
(``core/backward.py`` around ``core/sparsity.select``: importance, block
importance and top-k), over all backward work, each operation's time
split over the work it runs (``harness.phases``). Finds nothing to read, and returns nothing, where
the observation carries no phase attribution or no selection ran (a dense
step, or a program without the scope). Moves ``train_images_per_s``.
"""


def read(obs):
    ph = getattr(obs, "phases", None)
    if ph is None or ph.select_s <= 0 or ph.phase_s["backward"] <= 0:
        return None
    return 100.0 * ph.select_s / ph.phase_s["backward"]
