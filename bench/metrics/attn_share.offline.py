"""Attention's share of device time in the offline cells; see scopes."""
from scopes import attn_share as read  # noqa: F401
