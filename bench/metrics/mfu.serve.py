"""Model operations of the window, counted from real tokens, over the
chip's bf16 peak times the window, in per cent."""
from bench.readers import mfu as read  # noqa: F401
