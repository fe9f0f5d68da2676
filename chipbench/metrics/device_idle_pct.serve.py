"""1 - union of device-op intervals over the traced steady window."""
from chipbench.reduce import idle_pct as read  # noqa: F401
