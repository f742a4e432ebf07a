"""How late the load generator ran: max over bursts of (sent - due). A
starved generator must not read as a fast server."""
LAYER, UNIT = "load generator", "ms"


def read(run):
    return run["gen"].get("late_ms_max")
