"""Mean duration (ms) of the program's ``serving.plan_run`` spans in the
traced window: the host side of one ``ExecutionPlan.run`` call."""
from harness import spans


def read(r):
    if r.trace is None:
        return None
    return spans.mean_ms(r.trace["spans"], "serving.plan_run")
