"""compile_s: seconds of backend compilation, persistent-cache reads
included, from process start to the end of set-up (``jax.monitoring``'s
``backend_compile_duration``).  Moves ``setup_s``."""


def read(run):
    return sum(run.compile_log.between(0.0, run.setup_end))
