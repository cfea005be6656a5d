"""REST requests an epoch carried, over the epochs that carried any."""

from harness.program_trace import requests_per_epoch as read  # noqa: F401
