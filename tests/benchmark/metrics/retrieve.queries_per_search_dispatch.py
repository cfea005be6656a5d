"""Queries asked for over ``knn_search`` dispatches (the program's
registry, since the process began)."""

from harness.program_trace import registry_ratio as read  # noqa: F401
