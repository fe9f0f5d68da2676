"""Megabytes of parameters the mean decode step READ, as they are held:
``weight_bytes`` of the window's ``step`` records (each layer's once a pass
of the stack, the head once, a table row a slot). None on a program whose
steps do not report it (a block that declares no ``step_weight_bytes``,
or older than the field)."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "weight_bytes")
    if not rows:
        return None
    return sum(b for b, in rows) / len(rows) / 1e6
