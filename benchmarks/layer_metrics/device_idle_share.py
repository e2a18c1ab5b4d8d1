"""1 - (union of device operation intervals) / traced window, mean over
the chips used. The dotted names (``.train``, ``.gen``, ``.burst``) are
this one reader: cells that report different end-to-end metrics need a
name each."""


def read(ctx, result):
    tr = result.get("trace")
    if tr is None or not tr.device_ops:
        return None
    return 100.0 * tr.idle_share()
