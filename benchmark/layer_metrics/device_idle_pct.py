"""The share of the window in which the device ran no call, measured: 100
x (1 - the window's growth of the program's device busy seconds, summed
over kernels / the window's seconds). The program stamps every call of
the window (runtime/profiler.py, the device timeline): busy from
max(dispatch, the previous call's end) to its output being ready. None
where the program has no `verify_device_idle_seconds_total`: its
`verify_device_seconds_total` is then host deltas, not the device's."""
from benchmark import timeline_metrics as tm

LAYER, UNIT = "device", "%"


def read(run):
    if not run.get("window_s") or not tm.has(run, tm.IDLE):
        return None
    return 100.0 * (1.0 - tm.growth(run, tm.BUSY) / run["window_s"])
