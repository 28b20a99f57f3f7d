"""Per-lane useful share in the offline cells; see layer_reads."""
from layer_reads import lane_useful_frac as read  # noqa: F401
