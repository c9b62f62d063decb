"""Share of the HBM roofline that the ops reading the design A reach, in %.

For each device: bytes of A those ops read (``tracefile.a_bytes``, by
operand shape) over their summed device time, over the chip's peak HBM
bandwidth (``peaks.json``); the mean over devices. The ops are bound by
bandwidth (one multiply-add per element of A read), so bytes are the
roofline's bound. An op that reads A more than once is counted once, so
the share can only be too low, never above 100%.
"""
import tracefile


def read(view):
    tr = view.trace
    if tr is None:
        return None
    shares = []
    for d in tr.devices:
        nbytes, secs = 0, 0.0
        for op in tr.device_ops(d):
            b = tracefile.a_bytes(op, view.design_shape, view.chips)
            if b:
                nbytes += b
                secs += op.dur_ns * 1e-9
        if secs > 0:
            shares.append(nbytes / secs / view.peaks["hbm_bytes_per_s"])
    if not shares:
        return None
    return 100.0 * sum(shares) / len(shares)
