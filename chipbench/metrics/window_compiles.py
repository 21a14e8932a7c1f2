"""window_compiles: backend compiles (or persistent-cache reads) inside the
measured window; a warmed-up cell reads 0.  Moves ``round_p90_s``."""


def read(run):
    return len(run.compile_log.between(*run.window))
