"""The solver's own share of device time in the serve cells; see scopes."""
from scopes import solver_share as read  # noqa: F401
