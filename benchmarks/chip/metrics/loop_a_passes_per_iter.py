"""Passes over the design A per outer iteration inside the solver's loop
program (``repro_loop``): executions of ops that read A
(``tracefile.a_bytes`` > 0) in that program, over the outer iterations of
``view.fits``, which are the fits the trace holds; the mean over devices.
Needs a trace that keeps the device programs (``phases.ProgramTrace``);
``None`` where no program is named ``repro_loop``."""
import phases
import tracefile


def read(view):
    tr = view.trace
    iters = sum(f.iters for f in view.fits)
    if not hasattr(tr, "has_module") or not tr.has_module(phases.LOOP) \
            or not iters or not tr.devices:
        return None
    passes = [sum(1 for o in tr.device_ops(d)
                  if tr.module_of(o) == phases.LOOP
                  and tracefile.a_bytes(o, view.design_shape, view.chips))
              for d in tr.devices]
    return sum(passes) / len(passes) / iters
