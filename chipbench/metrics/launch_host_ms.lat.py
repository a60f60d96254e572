"""Mean duration (ms) of the program's ``serving.launch`` spans in the
traced window: one launch on the dispatch thread, from taking the bucket
to scattering its results."""
from harness import spans


def read(r):
    if r.trace is None:
        return None
    return spans.mean_ms(r.trace["spans"], spans.LAUNCH)
