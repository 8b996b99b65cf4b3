"""Reader `registry_at_window_start`: a histogram of the program's
MetricsRegistry as the measured window's START found it
(`sources["registry_before"]`, a `MetricsRegistry.snapshot()`): what the
process did in set-up, all of it.

spec: family, labels (subset match), statistic:
  sum_s   the matching series' sums, a family in ms, as seconds
  count   the matching series' observations

Nothing where the program has no such family (the parent of the PR that
brought it). Where the family is there and no series matches, 0: set-up
did none of it."""

from __future__ import annotations

from benchmark.readers.registry import _series


def read(spec, sources):
    before = sources.get("registry_before") or {}
    if spec["family"] not in before:
        return None
    series = _series(before, spec["family"], spec.get("labels", {}))
    if spec["statistic"] == "sum_s":
        return sum(s["sum"] for s in series) / 1e3
    if spec["statistic"] == "count":
        return float(sum(s["count"] for s in series))
    raise ValueError(
        f"unknown registry_at_window_start statistic {spec['statistic']!r}")
