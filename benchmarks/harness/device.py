"""The chip gate and the one table of peaks.

A measurement run needs a TPU and at least as many chips as its cell
asks for, and stops before any result otherwise: there is no CPU
fallback. The rehearsal asks for the CPU and says so in what it prints.
"""

import sys

from . import manifest


class NoChip(SystemExit):
    """Raised (exit code 3) when the devices are not what the cell needs."""

    def __init__(self, message):
        sys.stderr.write(f"benchmark: {message}\n")
        super().__init__(3)


def acquire(chips, rehearsal):
    """The ``chips`` devices the cell's mesh is built over, and the stamp
    every result carries: the device as JAX reports it."""
    import jax

    devices = jax.devices()
    platform = devices[0].platform
    want = "cpu" if rehearsal else "tpu"
    if platform != want:
        raise NoChip(f"needs platform {want!r}, jax found {platform!r} "
                     f"({devices[0].device_kind} x{len(devices)})")
    if len(devices) < chips:
        raise NoChip(f"the cell asks for {chips} chips, jax found "
                     f"{len(devices)}")
    stamp = {"platform": platform, "kind": devices[0].device_kind,
             "count": len(devices)}
    return devices[:chips], stamp


def peaks(device_kind):
    """Published peaks of one chip. A device that is not in the table is
    an error, never a default."""
    table = manifest.load_json(manifest.BENCH_DIR, "peaks.json")
    if device_kind not in table:
        raise KeyError(
            f"no peaks for device_kind {device_kind!r} in "
            f"benchmarks/peaks.json (has: {sorted(table)})")
    return table[device_kind]


def memory_peak_bytes(devices):
    """Peak bytes held on the fullest of ``devices``: the buffers in use
    at their peak plus what the runtime reserved for the programs'
    temporaries. On this TPU runtime ``peak_bytes_in_use`` counts only the
    first (arguments and results; 0.49 GB for a ResNet-50 step whose
    temporaries take 9.0 GB), and ``peak_bytes_reserved`` the second."""
    peak = 0
    for d in devices:
        stats = d.memory_stats() or {}
        print(f"[memory] device {d.id}: " + " ".join(
            f"{k}={v}" for k, v in sorted(stats.items())), flush=True)
        peak = max(peak, int(stats.get("peak_bytes_in_use", 0))
                   + int(stats.get("peak_bytes_reserved", 0)))
    return peak
