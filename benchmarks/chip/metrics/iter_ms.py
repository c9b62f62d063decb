"""Milliseconds per outer iteration: the window's time on the host clock
over the outer iterations of all its fits."""


def read(view):
    iters = sum(f.iters for f in view.fits)
    if not iters:
        return None
    return view.window_s / iters * 1e3
