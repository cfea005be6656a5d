"""Median time the engine worked on an epoch: it begins -> its
``on_time_end`` sweep is done (the program's ``epoch`` spans)."""

from harness.program_trace import span_metric_median as read  # noqa: F401
