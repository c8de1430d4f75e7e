"""``step_opt_ms``: device milliseconds per step in the optimizer update.

The traced window's work under the ``adam`` scope (``optim/adam.py``),
each operation's time split over the work it runs, summed and divided by
the traced steps (``harness.phases``). Finds nothing to read, and returns nothing, where
the observation carries no phase attribution or no operation carries the
scope. Moves ``train_images_per_s``.
"""
from bench.harness import phases


def read(obs):
    return phases.phase_ms(getattr(obs, "phases", None), "optimizer")
