"""Layer device_decode: the decode's share of its roofline, in per cent.

The decode is bound by memory bandwidth: it does a few integer operations
per byte. The least time the chip could take is the bytes the data needs
- the input file's bytes read once and the Arrow result's bytes
(`table.nbytes`) written once - over the peak HBM bandwidth of
peaks.json; the share is that over the device's busy time for the same
scans. Needed bytes come from the data, not from the padded launch, so
less padding raises the share and more padding lowers it.
"""
from ..manifest import load_json


def read(record: dict):
    trace = record.get("trace")
    if not trace or not trace["busy_s"] or not trace["needed_bytes"]:
        return None
    peaks = load_json("peaks.json")["devices"]
    kind = record["device"]["kind"]
    if kind not in peaks:
        raise KeyError(f"no peak for device kind {kind!r} in peaks.json")
    least_s = trace["needed_bytes"] / peaks[kind]["hbm_bytes_per_s"]
    return 100.0 * least_s / trace["busy_s"]
