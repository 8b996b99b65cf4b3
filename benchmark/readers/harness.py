"""Reader `harness`: a named number the runner computed
(`sources["harness"][key]`), such as the compile count in the window or
the MFU."""


def read(spec, sources):
    return (sources.get("harness") or {}).get(spec["key"])
