"""Self time of the prefill pieces' attention (scopes ``decoder.attn.*``,
``mla.expand``), over the pieces the traced runs carried, by the program's
executable table (``harness/device_scopes.py``)."""

from harness.device_scopes import ms_per_carry as read  # noqa: F401
