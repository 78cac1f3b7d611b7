"""Device peaks, keyed by JAX's ``device_kind`` (``bench/peaks.json``).

A device that is not in the table is an error, never a default.  The
clearing-pass roofline will divide by these once the clearing pass can
be attributed in the trace (``PERF.md``, open questions)."""
import json
import os

_PATH = os.path.join(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))), "peaks.json")


def peak(device_kind: str) -> dict:
    with open(_PATH) as f:
        devices = json.load(f)["devices"]
    if device_kind not in devices:
        raise KeyError(f"no peaks for device kind {device_kind!r}; "
                       f"known: {sorted(devices)}")
    return devices[device_kind]
