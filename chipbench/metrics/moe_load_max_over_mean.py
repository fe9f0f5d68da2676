"""How uneven the routing is: mean over the window's decode steps of the
most tokens on one held expert in one layer (``expert_load_max``) over the
mean load of a held expert in that step (``routed_here`` over held experts
x sparse layers). 1 is perfectly even."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "expert_load_max", "routed_here")
    slots = step_fields.held_slots(record["model"]) if rows else 0
    ratios = [top * slots / here for top, here in rows or [] if here]
    if not slots or not ratios:
        return None
    return sum(ratios) / len(ratios)
