"""Share of the traced window in which no operation ran on the device,
in per cent."""
from bench.readers import idle_share as read  # noqa: F401
