"""aggregate_ms: device milliseconds per round of the programs dispatched
by the aggregation policy and the broadcast (the ``aggregate`` and
``broadcast`` spans), from the profiler trace.  Moves ``tokens_per_s``."""


def read(run):
    by_span = (run.trace or {}).get("device_s_by_span", {})
    t = by_span.get("aggregate", 0.0) + by_span.get("broadcast", 0.0)
    if not t or not run.walls:
        return None
    return 1e3 * t / len(run.walls)
