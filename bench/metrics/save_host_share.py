"""Share of the save spans in which no operation ran on the device, in
percent: the part of a save that is host work."""

def read(run):
    t = run.trace
    if t is None or not t.devices:
        return None
    total, busy = t.busy_within("ckpt_save")
    return 100.0 * (1.0 - busy / total) if total > 0 else None
