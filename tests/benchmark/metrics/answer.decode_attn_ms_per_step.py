"""Self time of the decode chunks' attention (scopes ``decoder.attn.*``,
``mla.absorb``), over the steps the traced runs ran, by the program's
executable table (``harness/device_scopes.py``)."""

from harness.device_scopes import ms_per_carry as read  # noqa: F401
