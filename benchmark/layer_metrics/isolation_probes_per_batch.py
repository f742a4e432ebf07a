"""Device calls a descent makes: mean over the window's isolated batches
of the flight record's `probes` (a record is written when its batch is
done, so a descent cut by the window's edge is not counted in part: with
one forged vote in every batch of 64 this reads a whole number). None,
never a raise, where the record has no such field or no batch was
isolated."""
from benchmark import span_metrics

LAYER, UNIT = "firehose settle and delivery", "probes"


def read(run):
    probes = [r["probes"] for r in span_metrics.batch_rows(run)
              if r.get("probes")]
    return sum(probes) / len(probes) if probes else None
