"""Seconds from the process start to the end of set-up (loading, building the
state from the seed, compiling or reading the compile cache, warm-up)."""

def read(run):
    return run.setup_s
