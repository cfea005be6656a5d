"""Self time of the decode chunks' gated delta-rule layers (every scope
``kda.*``: projections and convolutions, gates, the step of the recurrence,
the output's norm and gate), over the steps the traced runs ran, by the
program's executable table (``harness/device_scopes.py``)."""

from harness.device_scopes import ms_per_carry as read  # noqa: F401
