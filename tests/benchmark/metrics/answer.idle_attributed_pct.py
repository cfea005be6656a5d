"""Share of the slice's device-idle time that falls under a named ``pw.``
region of the program (``harness/program_trace.py:attribute_idle``)."""

from harness.program_trace import idle_attributed_pct as read  # noqa: F401
