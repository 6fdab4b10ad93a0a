"""Device idle share of the offline cells' traced window, in %:
1 - (union of device-op intervals) / window."""
from readers import idle_share as read  # noqa: F401
