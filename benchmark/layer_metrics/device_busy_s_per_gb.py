"""Layer device_decode: seconds in which an operation ran on the device
(profiler trace) per 10^9 input bytes scanned inside the traced span."""
from ..harness import GB


def read(record: dict):
    trace = record.get("trace")
    if not trace or not trace["scanned_bytes"]:
        return None
    return trace["busy_s"] / trace["scanned_bytes"] * GB
