"""Per-chip peak numbers for roofline/MFU accounting (docs/ROOFLINE.md).

The program's one table of peaks, for `chip_smoke.py`, the serving
accountant (`observability/roofline.py`) and any profiling hook that
wants achieved-vs-peak ratios; the benchmark keeps its own
(`benchmark/peaks.json`). Values are the published per-chip peaks;
lookup is by `device_kind` substring, and a device the tables do not
list raises `UnknownDeviceError`: scripts fail on it, the library
gauges that divide by a peak stay unpublished."""

from __future__ import annotations

PEAK_BF16_FLOPS = [  # device_kind substring -> peak bf16 FLOP/s per chip
    ("v6", 918e12),
    ("v5p", 459e12),
    ("v5e", 197e12),
    ("v5 lite", 197e12),
    ("v4", 275e12),
    ("v3", 123e12),
    ("v2", 46e12),
]

PEAK_HBM_BYTES = [  # device_kind substring -> peak HBM bytes/s per chip
    ("v6", 1640e9),
    ("v5p", 2765e9),
    ("v5e", 819e9),
    ("v5 lite", 819e9),
    ("v4", 1228e9),
    ("v3", 900e9),
    ("v2", 700e9),
]


class UnknownDeviceError(LookupError):
    """The device's `device_kind` is in neither peak table (the CPU
    backend, a TPU generation not listed). There is no default: a rate
    divided by another chip's peak is not a utilization."""


def _lookup(device, table, what: str) -> float:
    kind = str(getattr(device, "device_kind", "")).lower()
    for sub, peak in table:
        if sub in kind:
            return peak
    raise UnknownDeviceError(
        f"no published {what} for device_kind {kind!r}; add it to "
        "utils/roofline.py with its source")


def peak_flops(device) -> float:
    """Peak bf16 matmul FLOP/s of one chip; UnknownDeviceError for a
    device_kind the table does not list."""
    return _lookup(device, PEAK_BF16_FLOPS, "peak bf16 FLOP/s")


def peak_hbm(device) -> float:
    """Peak HBM bytes/s of one chip; UnknownDeviceError for a
    device_kind the table does not list."""
    return _lookup(device, PEAK_HBM_BYTES, "peak HBM bytes/s")
