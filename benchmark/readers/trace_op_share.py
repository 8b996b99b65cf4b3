"""Reader `trace_op_share`: share (%) of device operation time in
operations whose name matches the metric's `pattern`. Only from a device
trace: the CPU rehearsal's stand-in is not reported under a device
metric's name."""


def read(spec, sources):
    red = sources.get("trace") or {}
    share = (red.get("op_share") or {}).get(spec["name"])
    if share is None or red.get("device_source") != "device" \
            or not red.get("busy_s"):
        return None
    return 100.0 * share
