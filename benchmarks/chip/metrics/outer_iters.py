"""Outer Bi-cADMM iterations to converge, averaged over the window's fits
(``result_.iters``: a counter of the solver loop)."""


def read(view):
    if not view.fits:
        return None
    return sum(f.iters for f in view.fits) / len(view.fits)
