"""Set-up seconds JAX spent tracing Python to jaxprs inside the program's
compile scope (paid on every start, whatever the cache holds)."""
from benchmark import span_metrics

LAYER, UNIT = "kernel", "s"


def read(run):
    return span_metrics.setup_phase_s(run, "trace")
