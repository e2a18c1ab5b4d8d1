"""Sequences advanced per device step over the window: for every serve
step, the sequences it scheduled (the scheduler's own count; for a
multi-step burst, the live sequences) weighted by the device steps it
ran. A count: it repeats exactly for the same schedule."""


def read(ctx, result):
    steps = result["served"].steps
    n = sum(s["device_steps"] for s in steps)
    if not n:
        return None
    return sum(s["seqs"] * s["device_steps"] for s in steps) / n
