"""Device idle share in the offline cells; see layer_reads."""
from layer_reads import device_idle as read  # noqa: F401
