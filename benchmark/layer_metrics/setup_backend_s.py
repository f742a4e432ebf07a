"""Set-up seconds inside the program's compile scope that JAX spent in the
backend: the XLA compile, or with a warm cache the executable's load."""
from benchmark import span_metrics

LAYER, UNIT = "kernel", "s"


def read(run):
    return span_metrics.setup_phase_s(run, "backend")
