"""An ESTIMATE of the share of the window in which no operation ran on the
device: 1 - (busy seconds of ONE traced batch, the union of every device
operation's interval between its submission and its verdict, taken after
the window with nothing else in flight) x (verify calls of the window,
from the program's flight records) / (the window's seconds, host clock).
Only the first factor comes from the trace; a kernel that runs slower
under the window's load, or device work outside the verify calls, is not
seen. The traced batch's own busy and window seconds are in the result's
`device`, from which the driver works out that batch's idle share."""
LAYER, UNIT = "device", "%"


def read(run):
    trace = run["trace"]
    if not trace or not trace["kernel_calls"] or not run["window_s"]:
        return None
    per_call = trace["busy_s"] / trace["kernel_calls"]
    return 100.0 * (1.0 - per_call * run["window_calls"] / run["window_s"])
