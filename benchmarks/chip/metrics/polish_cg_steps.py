"""CG steps of the polish per fit, the mean over the fits:
``counters["polish_cg_steps"]`` (the solver's own counter; Newton-CG:
summed over its Newton steps). ``None`` where a fit carries no counters
(an engine or program without them)."""


def read(view):
    steps = [getattr(f, "counters", None) for f in view.fits]
    if not steps or any(c is None for c in steps):
        return None
    return sum(c["polish_cg_steps"] for c in steps) / len(steps)
