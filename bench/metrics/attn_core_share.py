"""``attn_core_share``: the share of the step's device operation time
spent in attention's own work, the program's ``attn_core`` scope
(``models/layers.py::masked_attention``: scores, mask, softmax and the
product with V, forward and backward), each operation's time split over
the work it runs (``harness.lm.scope_seconds``), over all operations'
time (``harness.phases``). Finds nothing to read, and returns nothing,
where no operation carries the scope. Moves ``train_images_per_s``.
"""


def read(obs):
    scope_s = getattr(obs, "scope_s", None) or {}
    ph = getattr(obs, "phases", None)
    if ph is None or ph.op_s <= 0 or scope_s.get("attn_core", 0.0) <= 0:
        return None
    return 100.0 * scope_s["attn_core"] / ph.op_s
