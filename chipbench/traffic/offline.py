"""Closed back-to-back ``plan.run`` calls on one large batch shape, the
frontend bypassed (``harness.offline``)."""
from harness.offline import Offline as Traffic  # noqa: F401
