"""batch_feed_ms: host milliseconds per round inside the driver's
``batch_fn`` calls (drawing the batch and handing it to the device),
from the benchmark's host spans.  Moves ``tokens_per_s``."""


def read(run):
    rounds = len(run.walls)
    spans = run.spans.within("batch_fn", *run.window)
    if not rounds or not spans:
        return None
    return 1e3 * sum(e - s for s, e in spans) / rounds
