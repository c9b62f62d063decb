"""Share of the device's busy time spent in the polish program
(``repro_polish``): the union of its ops' intervals over the union of all
op intervals in the traced window, the mean over devices. Needs a trace
that keeps the device programs (``phases.ProgramTrace``); ``None`` where
no program is named ``repro_polish``."""
import phases


def read(view):
    tr = view.trace
    if not hasattr(tr, "has_module") or not tr.has_module(phases.POLISH):
        return None
    fracs = [tr.module_busy_s(d, phases.POLISH) / tr.busy_s(d)
             for d in tr.devices if tr.busy_s(d) > 0]
    return sum(fracs) / len(fracs) if fracs else None
