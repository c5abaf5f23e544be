"""Share of the traced seconds of the serving loop in which no
operation ran on the device."""
from ehbench.readers import device_idle as read  # noqa: F401
