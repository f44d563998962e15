"""The device's idle share in a serving cell."""
from perfbench.harness.readers import device_idle_share as read  # noqa: F401
