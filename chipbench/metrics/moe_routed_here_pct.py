"""Of the window's token-choices (tokens x experts per token x sparse
layers), the share that fell on experts held here: ``routed_here`` over
``routed_all`` of the ``step`` records. 100 x held / router width (12.5 for
16 of 128) under even routing."""
from chipbench import step_fields


def read(record):
    rows = step_fields.columns(record, "routed_here", "routed_all")
    if not rows or not sum(a for _, a in rows):
        return None
    return 100.0 * sum(h for h, _ in rows) / sum(a for _, a in rows)
