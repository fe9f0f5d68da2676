"""Model FLOPs of every token processed in the window over the window's
length times the chip's bf16 peak."""
from chipbench.reduce import serve_flops


def read(record):
    if not record.get("peaks"):
        return None
    flops = serve_flops(record)
    if not flops:
        return None
    return 100.0 * flops / (record["window"]["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
