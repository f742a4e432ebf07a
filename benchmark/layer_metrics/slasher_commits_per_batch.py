"""Storage transactions the slasher issued per batch: the window's growth
of the program's `slasher_storage_commits_total` (one for each `put` /
`put_batch` of the slasher; the feed is one slasher call a batch) over the
window's batches. None, never a raise, where the program has no such
counter (a program from before it)."""
from benchmark import observe, span_metrics

LAYER, UNIT = "firehose settle and delivery", "commits"
COMMITS = "slasher_storage_commits_total"


def read(run):
    batches = len(span_metrics.batch_rows(run))
    if not batches or not any(name == COMMITS for name, _ in run["after"]):
        return None
    return observe.series_delta(run["before"], run["after"], COMMITS) / batches
