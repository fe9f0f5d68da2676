"""Megabytes of recurrent state the mean decode step READ AND WROTE, as
they are stored: ``state_bytes`` of the window's ``step`` records (each
active slot's state of every ``state`` cache group once in and once out).
None on a program whose steps do not report it (no such group, or older
than the field)."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "state_bytes")
    if not rows:
        return None
    return sum(b for b, in rows) / len(rows) / 1e6
