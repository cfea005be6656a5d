"""Share of the search kernel's self time under its scope ``knn.top_k``
(both stages of the blocked top-k), by the program's executable table
(``harness/device_scopes.py``)."""

from harness.device_scopes import scope_share_pct as read  # noqa: F401
