"""The least time the chip could take for the window's mean decode step
(the larger of FLOPs over the bf16 peak and bytes over the HBM peak;
bytes = weights once + the live part of the K/V cache + one row written
per token) over the decode executable's mean device time in the trace."""
from chipbench.reduce import (counter_delta, decode_flops, decode_work,
                              itemsize, module_times)


def read(record):
    times = module_times(record, "decode")
    steps = counter_delta(record, "steps")
    if not times or not steps or not record.get("peaks"):
        return None
    fl, model = record["flops"], record["model"]
    tokens, ctx_sum = decode_work(record, record["t0"], record["t1"])
    flops = decode_flops(record, tokens, ctx_sum)
    nbytes = fl.decode_steps_bytes(model, steps, ctx_sum, tokens,
                                   itemsize(record))
    least, bound = fl.least_seconds(flops / steps, nbytes / steps,
                                    record["peaks"])
    record.setdefault("notes", {})["decode_step_bound"] = bound
    return 100.0 * least / (sum(times) / len(times))
