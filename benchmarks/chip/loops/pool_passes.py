"""Cold fits in whole passes over a pool of responses.

Parameters, from the mix's data file:

``pool``         the window fits responses 0 .. pool-1 of the
                 configuration's dataset;
``warmup_fits``  fits made in set-up, on responses beyond any pool.

``--seed`` permutes the pool. The window fits the pool in that order,
pass after pass, and starts a new pass only while less than ``--seconds``
has passed: every seed fits each response equally often, so the work in a
window does not depend on the seed, however fast a fit gets. Every fit is
cold, from the zero state, through the estimator's public ``fit``.
"""
from __future__ import annotations

import time

import numpy as np

# response indices of warm-up fits: far beyond any pool
WARMUP_BASE = 1 << 20


def order(mix: dict, seed: int) -> list[int]:
    """The pool's responses in the order ``seed`` draws."""
    return [int(k) for k in
            np.random.default_rng(seed).permutation(int(mix["pool"]))]


def warm_up(ses, mix: dict) -> None:
    if int(mix["warmup_fits"]) < 1:
        raise ValueError("warmup_fits must be at least 1: the window may "
                         "not compile")
    for j in range(int(mix["warmup_fits"])):
        ses.fit(WARMUP_BASE + j)


def window(ses, mix: dict, seed: int, seconds: float) -> list:
    ks = order(mix, seed)
    fits = []
    t0 = time.perf_counter()
    while not fits or time.perf_counter() - t0 < seconds:
        fits.extend(ses.fit(k) for k in ks)
    return fits


def traced(ses, mix: dict, seed: int) -> list:
    """The traced run records one fit: the window's first response."""
    return [ses.fit(order(mix, seed)[0])]
