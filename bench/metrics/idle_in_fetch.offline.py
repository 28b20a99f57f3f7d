"""Device idle under the round's blocking fetches in the offline cells;
see scopes."""
from scopes import idle_in_fetch as read  # noqa: F401
