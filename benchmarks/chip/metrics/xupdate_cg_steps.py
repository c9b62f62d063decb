"""CG steps per node and outer iteration in the x-update, the mean over
the fits: ``counters["xupdate_cg_steps"]`` (the solver's own counter,
summed over nodes and iterations; Newton-CG: over its Newton steps too)
over outer iterations x N nodes. ``None`` where a fit carries no
counters (an engine or program without them)."""


def read(view):
    n_nodes = view.design_shape[0]
    per_fit = []
    for f in view.fits:
        c = getattr(f, "counters", None)
        if c is None or not f.iters:
            return None
        per_fit.append(c["xupdate_cg_steps"] / (f.iters * n_nodes))
    return sum(per_fit) / len(per_fit) if per_fit else None
