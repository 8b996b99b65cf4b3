"""Reader `registry_gauge`: the value of a gauge of the program's
MetricsRegistry as the window's end found it
(`sources["registry_after"]`, a `MetricsRegistry.snapshot()`).

spec: family, labels (subset match). Nothing where the program has no
such gauge or no series matches (the parent of the PR that brought the
gauge); the first match where several do."""

from __future__ import annotations

from benchmark.readers.registry import _series


def read(spec, sources):
    series = _series(sources.get("registry_after"), spec["family"],
                     spec.get("labels", {}))
    return series[0]["value"] if series else None
