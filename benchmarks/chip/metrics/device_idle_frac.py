"""Share of the traced window in which no op runs on the device:
1 - (union of op intervals) / window, the mean over devices."""


def read(view):
    tr = view.trace
    if tr is None or not tr.devices or tr.window_s <= 0:
        return None
    return 1.0 - tr.mean_busy_s() / tr.window_s
