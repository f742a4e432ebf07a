"""Set-up seconds JAX spent lowering jaxprs to MLIR inside the program's
compile scope (paid on every start, whatever the cache holds)."""
from benchmark import span_metrics

LAYER, UNIT = "kernel", "s"


def read(run):
    return span_metrics.setup_phase_s(run, "lower")
