"""``step_fwd_ms``: device milliseconds per step in the forward pass.

The traced window's work whose ``op_name`` is neither backward
(``transpose(``) nor the optimizer (``adam``), each operation's time split
over the work it runs, summed and divided by the traced steps
(``harness.phases``). Finds nothing to read, and returns
nothing, where the observation carries no phase attribution. Moves
``train_images_per_s``.
"""
from bench.harness import phases


def read(obs):
    return phases.phase_ms(getattr(obs, "phases", None), "forward")
