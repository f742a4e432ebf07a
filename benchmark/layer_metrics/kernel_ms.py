"""Device time of the verify executable per call: durations of its module
events in the device trace over their count."""
LAYER, UNIT = "kernel", "ms"


def read(run):
    trace = run["trace"]
    if not trace or not trace["kernel_calls"]:
        return None
    return trace["kernel_s"] / trace["kernel_calls"] * 1000.0
