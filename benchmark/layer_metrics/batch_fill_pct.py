"""Mean over the window's batches of items / bucket (flight records)."""
LAYER, UNIT = "firehose batching", "%"


def read(run):
    rows = [r for r in run["flight"] if r["kind"] == "batch" and r["bucket"]]
    if not rows:
        return None
    return 100.0 * sum(r["items"] / r["bucket"] for r in rows) / len(rows)
