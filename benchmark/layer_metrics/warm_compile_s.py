"""Layer device_program: seconds the program spent building its device
programs during the warm-up (DeviceStats.compile_s: trace, lower and
compile, or the load from the persistent cache)."""


def read(record: dict):
    stats = [r["device"] for r in record["warm"]["requests"]
             if r.get("device")]
    if not stats:
        return None
    return sum(s["compile_s"] for s in stats)
