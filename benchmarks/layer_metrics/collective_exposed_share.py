"""Time in all-gather / reduce-scatter / all-reduce operations during
which no compute operation runs on that device, over the traced window,
on the worst device. Read only where the cell spans chips."""


def read(ctx, result):
    tr = result.get("trace")
    if tr is None or len(tr.device_ops) < 2:
        return None
    return 100.0 * tr.exposed_collective_share()
