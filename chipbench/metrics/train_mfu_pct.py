"""Forward + backward model FLOPs per step from shapes (no recomputation
counted) times the steps completed, over the window times the bf16 peak."""


def read(record):
    if not record.get("peaks"):
        return None
    flops = record["flops"].train_step_flops(
        record["model"], record["batch"], record["seq"]) * record["steps"]
    return 100.0 * flops / (record["window_s"]
                            * record["peaks"]["bf16_flops_per_s"])
