"""The least time the chip could take for the mean traced prefill, at the
true prompt lengths (FLOPs with the head applied once, bytes = weights
once + the prompt's K/V rows written), over the prefill executables' mean
device time in the trace."""
from chipbench.reduce import itemsize, module_times, prefills_in


def read(record):
    times = module_times(record, "prefill")
    if not times or not record.get("traced") or not record.get("peaks"):
        return None
    lens = prefills_in(record, *record["traced"])
    if not lens:
        return None
    fl, model = record["flops"], record["model"]
    least = bound = None
    total = 0.0
    for n in lens:
        least, bound = fl.least_seconds(
            fl.prefill_flops(model, n),
            fl.prefill_bytes(model, n, itemsize(record)), record["peaks"])
        total += least
    record.setdefault("notes", {})["prefill_bound"] = bound
    return 100.0 * (total / len(lens)) / (sum(times) / len(times))
