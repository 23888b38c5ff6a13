"""Layer device_program: seconds of the warm-up's compiles spent tracing
and lowering (DeviceStats.lower_s), the part of `warm_compile_s` that the
persistent cache does not keep."""


def read(record: dict):
    stats = [r["device"] for r in record["warm"]["requests"]
             if r.get("device") and "lower_s" in r["device"]]
    if not stats:
        return None
    return float(sum(s["lower_s"] for s in stats))
