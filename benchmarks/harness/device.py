"""The device a run is on: the table of published peaks, the refusal to
measure on anything else, and the memory peak."""

from __future__ import annotations

from dataclasses import dataclass

# Google Cloud TPU documentation, "TPU v5e" / system architecture pages;
# keyed by the exact ``device_kind`` JAX reports. A kind that is not here is
# an error, never a default.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16 * 2 ** 30},
}


class NoAccelerator(RuntimeError):
    pass


@dataclass
class Device:
    platform: str
    kind: str
    count: int
    devices: list

    @property
    def peaks(self) -> dict:
        try:
            return PEAKS[self.kind]
        except KeyError:
            raise KeyError(
                f"no published peaks for device_kind={self.kind!r}; add it "
                f"to benchmarks/harness/device.py with its source"
            ) from None

    def memory_peak_bytes(self) -> int:
        peak = 0
        for d in self.devices:
            stats = d.memory_stats() or {}
            peak = max(peak, int(stats.get("peak_bytes_in_use", 0)))
        return peak

    def report(self) -> dict:
        return {"platform": self.platform, "kind": self.kind,
                "count": self.count,
                "memory_peak_bytes": self.memory_peak_bytes()}


def require(chips: int, allow_cpu: bool = False) -> Device:
    """The first ``chips`` accelerators, or NoAccelerator. ``allow_cpu`` is
    for the repo's CPU tests of the harness; such a run prints no device
    metric (the caller sees platform 'cpu')."""
    import jax

    devs = jax.devices()
    platform = devs[0].platform
    if platform == "cpu" and not allow_cpu:
        raise NoAccelerator("JAX found no accelerator (platform 'cpu')")
    if len(devs) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chip(s), JAX found {len(devs)}"
        )
    devs = devs[:chips]
    return Device(platform, devs[0].device_kind, chips, devs)
