"""Reader `trace_op_roofline`: share (%) of its roofline reached by the
operations whose name matches the metric's `pattern`: the least time the
chips need for the operations and bytes the runner counted for the
traced call under the metric's `work` name
(`sources["traced_work"][work]`, from the model family's
`kernel_work_per_sample`), at the peaks of `benchmark/peaks.json`, over
the matched operations' seconds on the device. Only from a device
trace, and nothing where no operation matched or no work was counted."""

from benchmark import metrics


def read(spec, sources):
    red = sources.get("trace") or {}
    seconds = (red.get("op_seconds") or {}).get(spec["name"])
    work = (sources.get("traced_work") or {}).get(spec["work"])
    if not seconds or not work or red.get("device_source") != "device":
        return None
    return metrics.roofline_percent(work["flops"], work["bytes"], seconds,
                                    sources["device_kind"], sources["chips"])
