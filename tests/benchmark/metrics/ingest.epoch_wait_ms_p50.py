"""Median time a commit's epoch waited for the engine: first injection for
its time -> the epoch begins (the program's ``epoch`` spans)."""

from harness.program_trace import span_metric_median as read  # noqa: F401
