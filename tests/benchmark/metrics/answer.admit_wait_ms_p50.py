"""Median time a request waited in the decoder server's queue for a slot:
submitted -> ``admit`` (the program's ``decode`` spans of the window)."""

from harness.program_trace import span_metric_median as read  # noqa: F401
