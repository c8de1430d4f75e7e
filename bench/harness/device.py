"""The accelerator a run measures: found, counted and read, never assumed."""
from __future__ import annotations


class NoAccelerator(RuntimeError):
    pass


def devices(chips: int, *, require_accelerator: bool = True) -> list:
    """The first ``chips`` devices; raises unless they are TPUs."""
    import jax

    found = jax.devices()
    if require_accelerator and found[0].platform != "tpu":
        raise NoAccelerator(f"JAX finds no TPU, only {found[0].platform} devices")
    if len(found) < chips:
        raise NoAccelerator(f"the cell asks for {chips} chips, JAX finds {len(found)}")
    return found[:chips]


def memory_peak(devs) -> int:
    """Peak bytes on the fullest device: its arrays' peak in use plus the
    peak that it reserved apart for the executables' temporaries, which
    ``peak_bytes_in_use`` leaves out (0 where not reported)."""
    peaks = []
    for d in devs:
        stats = d.memory_stats() or {}
        peaks.append(stats.get("peak_bytes_in_use", 0) + stats.get("peak_bytes_reserved", 0))
    return int(max(peaks))


def describe(devs) -> dict:
    d = devs[0]
    return {"platform": d.platform, "kind": d.device_kind, "count": len(devs)}
