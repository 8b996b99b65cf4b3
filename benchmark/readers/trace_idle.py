"""Reader `trace_idle`: 100 * (1 - union of device-op intervals / traced
window), from `trace_reduce.reduce_trace`. Only from a device trace: the
CPU rehearsal's stand-in is not reported under a device metric's name."""


def read(spec, sources):
    red = sources.get("trace") or {}
    if red.get("device_source") != "device":
        return None
    if not red.get("window_s"):
        return None
    return 100.0 * red["idle_share"]
