"""Median time from a request's submission to the decoder server to its
first token drained on the host (the program's ``decode`` spans of the
window): queue, prefill pieces, the first decode chunk."""

from harness.program_trace import span_metric_median as read  # noqa: F401
