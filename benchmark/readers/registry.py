"""Reader `registry`: a number from the program's MetricsRegistry over
the measured window. `sources["registry_before"]` / `["registry_after"]`
are `MetricsRegistry.snapshot()`s taken at the window's ends.

spec: family, labels (subset match), statistic:
  sum_over_window_pct  histogram sum (ms) grown in the window / window;
                       with `absent_is_zero`, a family that was never
                       observed reads 0 and not nothing"""

from __future__ import annotations


def _series(snapshot, family, labels):
    fam = (snapshot or {}).get(family)
    if not fam:
        return []
    return [s for s in fam["series"]
            if all(s["labels"].get(k) == str(v) for k, v in labels.items())]


def read(spec, sources):
    before, after = sources.get("registry_before"), \
        sources.get("registry_after")
    if after is None:
        return None
    family, labels = spec["family"], spec.get("labels", {})
    if spec["statistic"] != "sum_over_window_pct":
        raise ValueError(f"unknown registry statistic {spec['statistic']!r}")
    if not _series(after, family, labels):
        return 0.0 if spec.get("absent_is_zero") else None
    grown = sum(s["sum"] for s in _series(after, family, labels)) \
        - sum(s["sum"] for s in _series(before, family, labels))
    return 100.0 * grown / (sources["window_s"] * 1e3)
