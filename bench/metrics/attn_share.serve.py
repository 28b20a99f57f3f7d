"""Attention's share of device time in the serve cells; see scopes."""
from scopes import attn_share as read  # noqa: F401
