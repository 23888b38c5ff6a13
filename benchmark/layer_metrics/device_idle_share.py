"""Layer device: the share of the traced window in which no operation
ran on the device, 1 - busy / window (profiler trace)."""


def read(record: dict):
    trace = record.get("trace")
    if not trace:
        return None
    return 1.0 - trace["busy_s"] / trace["window_s"]
