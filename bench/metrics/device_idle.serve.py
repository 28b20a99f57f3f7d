"""Device idle share in the serve cells; see layer_reads."""
from layer_reads import device_idle as read  # noqa: F401
