"""Share of the device's busy time in which a collective runs: the union
of the intervals of its collective ops (``tracefile.is_collective``, by
opcode) over the union of all its op intervals, the mean over devices. A
TPU runs one op at a time, so this is the exposed exchange between chips,
waits for the slowest chip included. ``None`` where no device ran a
collective."""
import tracefile


def read(view):
    tr = view.trace
    if tr is None:
        return None
    lo, hi = tr.window
    fracs, seen = [], False
    for d in tr.devices:
        busy = tr.busy_s(d)
        spans = [(max(o.start_ns, lo), min(o.end_ns, hi))
                 for o in tr.device_ops(d) if tracefile.is_collective(o)]
        seen = seen or bool(spans)
        if busy > 0:
            fracs.append(sum(b - a for a, b in tracefile.union(spans))
                         * 1e-9 / busy)
    return sum(fracs) / len(fracs) if seen and fracs else None
