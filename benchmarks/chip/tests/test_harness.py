"""A whole run of a cell at a size the CPU holds, with the look for a chip
skipped: ``correct`` holds on the healthy program and comes out false with
each fault the cells can have planted in the timed path."""
import json
import os

import jax
import jax.numpy as jnp
import pytest

import harness
import traffic

ROOT = os.path.join(os.path.dirname(__file__), "..", "..", "..")


def tiny(loss):
    """A cell's configuration with its scale cut to a CPU test: the
    squared loss keeps the PCG x-update of the Table-1 cell."""
    name = {"squared": "table1_squared", "logistic": "slogr_table1"}[loss]
    with open(os.path.join(ROOT, "benchmarks", "chip", "configs",
                           f"{name}.json")) as f:
        cfg = json.load(f)
    cfg.update(m_per_node=1024, n_features=128)
    if loss == "squared":
        cfg["options"] = dict(cfg["options"], x_solver="pcg")
    return cfg


def run_cell(cfg, seed=2 ** 31 + 3):
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    cell = {"name": "tiny", "chips": 1}
    return harness.run(bench, cell, cfg, traffic.load("cold_fits"), seed,
                       0.0, False, 0.0, devs=jax.devices(), cache=False)


@pytest.mark.parametrize("loss", ["squared", "logistic"])
def test_healthy_run_is_correct(loss):
    out = run_cell(tiny(loss))
    assert out["correct"], out["checks"]
    # one whole pass over the pool of two, though the window is 0 s
    assert out["attempted"] == 2 and out["failed"] == 0
    assert set(out["metrics"]) == {"fit_s", "peak_hbm_gb", "setup_s"}
    assert list(out)[-1] == "checks"


def _state_unchanged(monkeypatch):
    from repro.core import bicadmm
    monkeypatch.setattr(bicadmm.BiCADMM, "_step",
                        lambda self, f, A, b, p, st: st._replace(k=st.k + 1))


def _half_the_rows(monkeypatch):
    from repro import api
    stack = api._stack

    def half(X, y):
        As, bs = stack(X, y)
        return As[:, : As.shape[1] // 2], bs[:, : bs.shape[1] // 2]
    monkeypatch.setattr(api, "_stack", half)


def _answer_altered(monkeypatch):
    from repro.core import bicadmm
    polish = bicadmm.BiCADMM._polish

    def altered(self, *args):
        x = polish(self, *args)
        j = jnp.argmax(jnp.abs(x))
        return x.at[j].multiply(1.01)
    monkeypatch.setattr(bicadmm.BiCADMM, "_polish", altered)


@pytest.mark.parametrize("loss", ["squared", "logistic"])
@pytest.mark.parametrize("fault", [_state_unchanged, _half_the_rows,
                                   _answer_altered])
def test_fault_is_not_correct(monkeypatch, loss, fault):
    fault(monkeypatch)
    out = run_cell(tiny(loss))
    assert not out["correct"], out["checks"]


def test_compile_in_window_is_not_correct(monkeypatch):
    """A response made by a new program after the warm-up compiles inside
    the window, which fails the run."""
    import data
    make = data.make_response
    calls = []

    def growing(A, seed, index, cfg):
        calls.append(index)
        b, x = make(A, seed, index, cfg)
        return (b, x) if len(calls) == 1 else (jax.jit(lambda v: v)(b), x)
    monkeypatch.setattr(data, "make_response", growing)
    cfg = tiny("squared")
    out = run_cell(cfg)
    assert out["checks"]["window_compiles"]["value"] > 0
    assert not out["correct"]

