"""Kind ``serve-open``: open-loop arrivals at the traffic file's fixed
rate; the tails of first-token and per-token time are what is judged.
The work is shared with the closed loop (drivers/serve.py); the traffic
file's kind picks how the load generator sends."""

from benchmark.drivers.serve import run  # noqa: F401
