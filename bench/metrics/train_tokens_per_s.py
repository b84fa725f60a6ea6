"""Tokens of completed steps over the whole window, saves and GC included."""

def read(run):
    if not run.job.steps_done:
        return None
    return run.job.tokens / run.window_s
