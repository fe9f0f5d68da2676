"""Megabytes of held-expert weights the mean decode step READ, as they are
held: ``expert_bytes`` of the window's ``step`` records (held experts that
at least one of the step's tokens chose, summed over the expert layers,
times the bytes of one held expert). None on a program whose steps do not
report it (a block with no expert layer, or older than the field)."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "expert_bytes")
    if not rows:
        return None
    return sum(b for b, in rows) / len(rows) / 1e6
