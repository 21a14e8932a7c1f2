"""step_ms: device milliseconds per local step of the programs the engine
step dispatched (the ``step`` span), from the profiler trace.  Moves
``tokens_per_s``."""


def read(run):
    t = (run.trace or {}).get("device_s_by_span", {}).get("step")
    if not t or not run.step_calls:
        return None
    return 1e3 * t / run.step_calls
