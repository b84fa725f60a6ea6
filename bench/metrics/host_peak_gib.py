"""Peak resident host memory of the process (ru_maxrss) at the close of the
window, in GiB: what a host OOM would kill."""

def read(run):
    return run.host_peak_gib
