"""Share of the device's busy time spent in ops that read the design A
(union of their intervals over the union of all op intervals; mean over
devices)."""
import tracefile


def read(view):
    tr = view.trace
    if tr is None:
        return None
    fracs = []
    for d in tr.devices:
        busy = tr.busy_s(d)
        a_ops = [(op.start_ns, op.end_ns) for op in tr.device_ops(d)
                 if tracefile.a_bytes(op, view.design_shape, view.chips)]
        if busy > 0 and a_ops:
            a_busy = sum(b - a for a, b in tracefile.union(a_ops)) * 1e-9
            fracs.append(a_busy / busy)
    return sum(fracs) / len(fracs) if fracs else None
