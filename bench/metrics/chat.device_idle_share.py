"""Share of the traced part with no operation on the device (%),
averaged over the chips."""

from bench.trace import read_idle_share as read  # noqa: F401
