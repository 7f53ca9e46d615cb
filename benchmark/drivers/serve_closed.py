"""Kind ``serve-closed``: a fixed number of callers, each waiting for
its reply; tokens per second that reached the clients is what is
judged. The work is shared with the open loop (drivers/serve.py)."""

from benchmark.drivers.serve import run  # noqa: F401
