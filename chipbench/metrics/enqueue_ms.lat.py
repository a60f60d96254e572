"""Mean duration (ms) of the program's ``serving.enqueue`` spans in the
traced window: the call of a bucket's plan entry to its return, its
Python and XLA's dispatch."""
from harness import spans


def read(r):
    if r.trace is None:
        return None
    return spans.mean_ms(r.trace["spans"], "serving.enqueue")
