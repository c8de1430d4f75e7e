"""Required work of one ResNet training step, from the configuration file.

The walk follows the geometry the classifier step executes: the stem as
the configuration file states it (kernel, stride, max-pool), then the
residual stages. Counts are in FLOPs (a multiply-add is 2) and bytes.

* :func:`paper_backward_flops`: the paper's Eq. 6/7 backward count (conv
  ``M*(4*C_in*K^2 + 1)*C_out`` plus BatchNorm ``12*M*C + 10*C``), which
  reproduces its Table 4 per-iteration numbers.
* :func:`step_flops`: the matmul work a step requires: every conv and the
  head forward, then dW of every conv and dX of every conv but the stem
  (the images take no gradient), at the kept output channels. Recomputed
  work and elementwise work do not count.
* :func:`sparse_kernel_calls`: the kept-channel backward contractions
  (dX and dW of each conv site), with the least bytes each must move:
  every operand read once, every output written once.
"""
from __future__ import annotations

import dataclasses
from collections.abc import Iterator


@dataclasses.dataclass(frozen=True)
class Conv:
    site: str
    c_in: int
    c_out: int
    k: int
    stride: int
    h_in: int
    w_in: int
    h_out: int
    w_out: int


def conv_sites(config: dict) -> Iterator[Conv]:
    """Every conv of the ResNet in execution order (basic blocks)."""
    if config["layout"] != "basic":
        raise ValueError(f"walk covers basic-block ResNets, not {config['layout']!r}")
    c, h, w = config["image"]
    stem = config["stem"]
    k, s = stem["kernel"], stem["stride"]
    pad = k // 2
    ho = (h + 2 * pad - k) // s + 1
    wo = (w + 2 * pad - k) // s + 1
    widths = config["widths"]
    yield Conv("stem", c, widths[0], k, s, h, w, ho, wo)
    if stem["maxpool"]:
        ho, wo = -(-ho // 2), -(-wo // 2)
    c_in, bi = widths[0], 0
    for si, (n, width) in enumerate(zip(config["stages"], widths, strict=True)):
        for b in range(n):
            st = 2 if (b == 0 and si > 0) else 1
            h2, w2 = (ho - 1) // st + 1, (wo - 1) // st + 1
            yield Conv(f"block_{bi}/conv1", c_in, width, 3, st, ho, wo, h2, w2)
            yield Conv(f"block_{bi}/conv2", width, width, 3, 1, h2, w2, h2, w2)
            if st != 1 or c_in != width:
                yield Conv(f"block_{bi}/down", c_in, width, 1, st, ho, wo, h2, w2)
            c_in, ho, wo, bi = width, h2, w2, bi + 1


def kept_channels(c_out: int, policy: dict) -> int:
    """Output channels whose gradient the backward contracts.

    Block granularity keeps ``max(1, round((1-D) * ceil(C/bs)))`` blocks;
    a ragged tail block's phantom slots are no channels and do not count.
    """
    d = policy["drop_rate"]
    if d <= 0.0:
        return c_out
    if policy["granularity"] == "channel":
        return max(1, int(round((1.0 - d) * c_out)))
    bs = policy["block_size"]
    nb = -(-c_out // bs)
    kb = max(1, int(round((1.0 - d) * nb)))
    return min(kb * bs, c_out)


def paper_backward_flops(config: dict, batch: int) -> int:
    """Eq. 6 (convs) plus Eq. 7 (their BatchNorms), dense, per iteration."""
    total = 0
    for cv in conv_sites(config):
        m = batch * cv.h_out * cv.w_out
        total += m * (4 * cv.c_in * cv.k * cv.k + 1) * cv.c_out
        total += 12 * m * cv.c_out + 10 * cv.c_out
    return total


def _contraction(cv: Conv, batch: int, cols: int) -> int:
    """FLOPs of one conv contraction over ``cols`` output channels."""
    return 2 * batch * cv.h_out * cv.w_out * cv.c_in * cv.k * cv.k * cols


def step_flops(config: dict, batch: int, policy: dict) -> int:
    """Required matmul FLOPs of one training step."""
    total = 0
    for cv in conv_sites(config):
        kept = kept_channels(cv.c_out, policy)
        total += _contraction(cv, batch, cv.c_out)  # forward
        total += _contraction(cv, batch, kept)  # dW
        if cv.site != "stem":
            total += _contraction(cv, batch, kept)  # dX
    head = 2 * batch * config["widths"][-1] * config["n_classes"]
    return total + 3 * head  # forward, dW, dX


@dataclasses.dataclass(frozen=True)
class KernelCall:
    site: str
    grad: str  # "dx" | "dw"
    flops: int
    bytes: int

    def least_s(self, peak_flops: float, peak_bytes_per_s: float) -> float:
        return max(self.flops / peak_flops, self.bytes / peak_bytes_per_s)

    def bound(self, peak_flops: float, peak_bytes_per_s: float) -> str:
        compute = self.flops / peak_flops >= self.bytes / peak_bytes_per_s
        return "compute" if compute else "memory"


def sparse_kernel_calls(
    config: dict, batch: int, policy: dict, itemsize: int = 4
) -> list[KernelCall]:
    """The kept-channel backward contractions of one step, one per call."""
    calls = []
    for cv in conv_sites(config):
        kept = kept_channels(cv.c_out, policy)
        x = batch * cv.c_in * cv.h_in * cv.w_in
        dy = batch * kept * cv.h_out * cv.w_out
        w = kept * cv.c_in * cv.k * cv.k
        flops = _contraction(cv, batch, kept)
        calls.append(KernelCall(cv.site, "dw", flops, (x + dy + w) * itemsize))
        if cv.site != "stem":
            calls.append(KernelCall(cv.site, "dx", flops, (dy + w + x) * itemsize))
    return calls
