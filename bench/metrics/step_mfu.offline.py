"""Whole-step MFU in the offline cells; see layer_reads."""
from layer_reads import step_mfu as read  # noqa: F401
