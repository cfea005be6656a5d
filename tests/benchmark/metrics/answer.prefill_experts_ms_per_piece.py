"""Self time of the prefill pieces' expert walk (scope ``moe.experts``), over
the pieces the traced runs carried (a lane of a group is one), by the
program's executable table (``harness/device_scopes.py``)."""

from harness.device_scopes import ms_per_carry as read  # noqa: F401
