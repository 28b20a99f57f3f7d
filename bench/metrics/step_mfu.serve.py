"""Whole-step MFU in the serve cells; see layer_reads."""
from layer_reads import step_mfu as read  # noqa: F401
