"""The device a run is on: refuse anything but the accelerator, name it on
the result line, look its peaks up (an unknown kind is an error)."""
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


class NoAccelerator(RuntimeError):
    """JAX found no accelerator, or fewer chips than the cell asks for."""


def require_chips(chips):
    """The first ``chips`` accelerator devices; raises :class:`NoAccelerator`
    where JAX has only the CPU or too few chips.  There is no CPU fallback:
    a number from the host is never a device metric."""
    import jax
    try:
        devices = jax.devices()
    except RuntimeError as e:
        raise NoAccelerator(f"JAX found no backend: {e}") from e
    if not devices or devices[0].platform == "cpu":
        raise NoAccelerator(
            f"JAX's default backend is {devices[0].platform if devices else None!r}: "
            f"the benchmark measures only on an accelerator")
    if len(devices) < chips:
        raise NoAccelerator(
            f"the cell asks for {chips} chips, JAX has {len(devices)}")
    return devices[:chips]


def allocator_peak_bytes(devices):
    """The allocator's ``peak_bytes_in_use`` on the fullest chip so far."""
    return int(max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
                   for d in devices))


def describe(devices, memory_peak_bytes):
    """``device`` of the result line, as JAX reports it.  The memory peak is
    the driver's reading of the PROGRAM's peak, taken before the plain
    reference put anything on the chip."""
    return {"platform": devices[0].platform, "kind": devices[0].device_kind,
            "count": len(devices),
            "memory_peak_bytes": int(memory_peak_bytes)}


def peaks_for(device_kind):
    """Published peaks of one chip of ``device_kind``; KeyError names the
    table where the kind is not in it."""
    with open(os.path.join(HERE, "peaks.json")) as f:
        table = json.load(f)
    try:
        return dict(table["devices"][device_kind], source=table["source"])
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in perf/harness/peaks.json "
            f"({sorted(table['devices'])}): add its published peaks with "
            f"their source, do not default") from None
