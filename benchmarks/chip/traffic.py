"""The traffic generator. A mix is a data file, ``traffic/<name>.json``, of
parameters; its ``loop`` names the module under ``loops/`` that drives the
window with them. Both are found by name, so a later mix adds a data file,
and a loop module only where none it needs exists yet, and edits neither
this file nor the harness.

A loop module has

``warm_up(ses, mix)``
    set-up's fits, on responses the window never uses;
``window(ses, mix, seed, seconds) -> list[FitRecord]``
    the measured fits, made from ``--seed``;
``traced(ses, mix, seed) -> list[FitRecord]``
    what the ``--trace 1`` run records after the window;

where ``ses`` is the run's ``harness.Session`` (configuration, design,
estimator, and ``fit(k)``, a cold fit of response k inside the harness's
spans).
"""
from __future__ import annotations

import importlib
import json
import os

HERE = os.path.dirname(os.path.abspath(__file__))


def load(name: str) -> dict:
    with open(os.path.join(HERE, "traffic", f"{name}.json")) as f:
        return json.load(f)


def loop(mix: dict):
    """The module that drives ``mix``'s window."""
    return importlib.import_module(f"loops.{mix['loop']}")
