"""The device gate, the table of peaks, and the compile counter."""
from __future__ import annotations

import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def require_tpu(chips: int) -> dict:
    """What jax reports, or SystemExit: there is no CPU continuation, and a
    cell is not run on fewer chips than it asks for."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise SystemExit(f"benchmark: no TPU: jax {jax.__version__} found "
                         f"platform={devs[0].platform!r}")
    if len(devs) < chips:
        raise SystemExit(f"benchmark: the cell asks for {chips} chips, jax "
                         f"found {len(devs)}")
    peaks(devs[0].device_kind)      # an unknown device is an error, early
    return {"platform": devs[0].platform, "kind": devs[0].device_kind,
            "count": len(devs)}


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip, keyed by a substring of ``device_kind``;
    a device that is not in ``peaks.json`` is an error, not a default."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)["devices"]
    for key, row in table.items():
        if key.lower() in device_kind.lower():
            return row
    raise SystemExit(f"benchmark: no peaks recorded for device_kind "
                     f"{device_kind!r}; add it to peaks.json with its source")


def memory_peak_bytes(chips: int) -> int:
    """Peak bytes in use on the fullest of the chips used."""
    import jax
    stats = [d.memory_stats() or {} for d in jax.devices()[:chips]]
    return max(int(s.get("peak_bytes_in_use", 0)) for s in stats)


class CompileMeter:
    """Counts jax's backend compilations (``chip_smoke.CompileMeter``,
    copied and cut down). One per process: listeners cannot be removed."""

    EVENT = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        import jax
        self.count = 0
        self.seconds = 0.0
        self.marked = 0              # the count when the window opened
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def mark(self) -> None:
        self.marked = self.count

    def _on(self, event, duration, **kw):
        if event == self.EVENT:
            self.count += 1
            self.seconds += duration
