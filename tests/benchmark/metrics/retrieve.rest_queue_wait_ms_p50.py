"""Median time a REST request waited before the epoch that answered it
began: arrival -> ``admit`` (the program's ``rest`` spans)."""

from harness.program_trace import span_metric_median as read  # noqa: F401
