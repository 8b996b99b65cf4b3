"""Reader `memory_stats`: peak bytes held on the fullest chip during
the window (`harness.memory_peak_bytes`: in use + reserved), in GB.
Nothing where the backend reports none (the CPU)."""


def read(spec, sources):
    peak = sources.get("memory_peak_bytes")
    return peak / 1e9 if peak else None
