"""Open-loop single-row requests through the frontend, below the knee: the
latencies are judged (``harness.openloop``)."""
from harness.openloop import OpenLoop as Traffic  # noqa: F401
