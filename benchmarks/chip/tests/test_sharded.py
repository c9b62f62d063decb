"""A whole run of a cell over a four-chip mesh at a size the CPU holds, on
four virtual devices in a subprocess: ``correct`` holds on the healthy
sharded fit, with the design's node blocks on four devices, and comes out
false with each fault such a cell can have planted in the timed path.
Beside it the reference summed from per-device blocks equals the
reference over the same data on one device, and the harness refuses a
cell whose chips and mesh disagree."""
import json
import os
import subprocess
import sys

import jax
import pytest

import harness
import traffic
from test_harness import ROOT, tiny

CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
FAULTS = ("state_unchanged", "half_the_rows", "exchange_left_out",
          "coef_perturbed", "support_altered")


def tiny_x4():
    """``table1_squared`` with each node's rows on its own device of a
    (nodes 4, feat 1) mesh, fitted by the sharded engine's PCG x-update,
    at a size the CPU holds. The sharded engine returns the consensus
    iterate without the polish, so its coefficient limit is wider than
    the one-chip cell's (PERF.md)."""
    cfg = tiny("squared")
    cfg.update(mesh={"nodes": 4, "feat": 1}, m_per_node=512)
    cfg["options"] = dict(cfg["options"], engine="sharded", x_update="cg")
    cfg["options"].pop("x_solver")
    cfg["limits"] = dict(cfg["limits"], coef_gap=1e-4)
    return cfg


SCRIPT = r"""
import json, sys
from unittest import mock
import numpy as np
import jax, jax.numpy as jnp
sys.path.insert(0, {src!r})
sys.path.insert(0, {chip!r})
import data, harness, reference, traffic
from repro import api
from repro.core import sharded

cfg = json.loads({cfg!r})
with open({bench!r}) as f:
    bench = json.load(f)
cell = {{"name": "tiny_x4", "chips": 4}}
mix = traffic.load("cold_fits")
devs = jax.devices()


def run():
    return harness.run(bench, cell, cfg, mix, 2 ** 31 + 11, 0.0, False, 0.0,
                       devs=devs, cache=False)


def state_unchanged():
    funcs = sharded.ShardedBiCADMM._local_funcs

    def frozen(self, *a):
        _, reset = funcs(self, *a)
        return (lambda st, kappa: st._replace(k=st.k + 1)), reset
    return mock.patch.object(sharded.ShardedBiCADMM, "_local_funcs", frozen)


def half_the_rows():
    stack = api._stack

    def half(X, y):
        As, bs = stack(X, y)
        return As[:, : As.shape[1] // 2], bs[:, : bs.shape[1] // 2]
    return mock.patch.object(api, "_stack", half)


def exchange_left_out():
    psum = sharded._psum
    return mock.patch.object(
        sharded, "_psum",
        lambda ax: (lambda x: x) if ax == "nodes" else psum(ax))


def _result(change):
    fit = sharded.ShardedBiCADMM.fit

    def altered(self, *a, **kw):
        return change(fit(self, *a, **kw))
    return mock.patch.object(sharded.ShardedBiCADMM, "fit", altered)


def coef_perturbed():
    def change(r):
        j = jnp.argmax(jnp.abs(r.coef[:, 0]))
        return r._replace(coef=r.coef.at[j, 0].multiply(1.01))
    return _result(change)


def support_altered():
    def change(r):
        c = np.array(r.coef[:, 0])
        on = np.flatnonzero(c)
        small, free = on[np.argmin(np.abs(c[on]))], np.flatnonzero(c == 0)[0]
        c[free], c[small] = c[small], 0.0
        return r._replace(coef=jnp.asarray(c)[:, None],
                          support=jnp.asarray(c != 0))
    return _result(change)


out = {{}}
mesh = harness.make_mesh(cfg, 4, devs)
A = harness.make_design(cfg, mesh)
out["design_devices"] = len(A.sharding.device_set)
out["shard_shape"] = list(A.addressable_shards[0].data.shape)
out["healthy"] = run()
for name in {faults!r}:
    with globals()[name]():
        out[name] = run()

# the reference from per-device blocks against one device's
b, _ = data.make_response(A, cfg["data_seed"], 0, cfg)
A1 = jax.device_put(A, devs[0])
b1 = jax.device_put(b, devs[0])
out["blocks"] = [len(reference._node_blocks(A)),
                 len(reference._node_blocks(A1))]
G, G1 = np.asarray(reference.gram(A)), np.asarray(reference.gram(A1))
out["gram_gap"] = float(np.max(np.abs(G - G1)) / np.max(np.abs(G1)))
kap, gam = data.kappa(cfg), cfg["solver"]["gamma"]
c4, s4 = reference.squared(A, b, gam, kap)
c1, s1 = reference.squared(A1, b1, gam, kap)
out["ref_coef_gap"] = float(np.max(np.abs(c4 - c1)) / np.max(np.abs(c1)))
out["ref_same_support"] = bool(np.array_equal(s4, s1))
print(json.dumps(out))
"""


@pytest.fixture(scope="module")
def four_devices():
    """Every run of the tiny four-device cell, made in one subprocess (the
    virtual devices must be set before JAX starts)."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SCRIPT.format(src=os.path.join(ROOT, "src"), chip=CHIP,
                         cfg=json.dumps(tiny_x4()), faults=FAULTS,
                         bench=os.path.join(ROOT, "BENCHMARK.json"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=1200)
    assert res.returncode == 0, res.stderr[-4000:]
    return json.loads(res.stdout.strip().splitlines()[-1])


def test_sharded_run_is_correct(four_devices):
    out = four_devices["healthy"]
    assert four_devices["design_devices"] == 4
    assert four_devices["shard_shape"] == [1, 512, 128]
    assert out["correct"], out["checks"]
    assert out["device"]["count"] == 4
    assert out["attempted"] == 2 and out["failed"] == 0
    assert list(out)[-1] == "checks"


@pytest.mark.parametrize("fault", FAULTS)
def test_sharded_fault_is_not_correct(four_devices, fault):
    out = four_devices[fault]
    assert not out["correct"], out["checks"]


def test_reference_from_blocks_equals_one_device(four_devices):
    assert four_devices["blocks"] == [4, 1]
    assert four_devices["gram_gap"] <= 1e-6, four_devices
    assert four_devices["ref_coef_gap"] <= 1e-5, four_devices
    assert four_devices["ref_same_support"]


@pytest.mark.parametrize("chips,mesh", [(4, None), (2, {"nodes": 4,
                                                           "feat": 1}),
                                        (1, {"nodes": 2, "feat": 2})])
def test_chips_and_mesh_must_agree(chips, mesh):
    cfg = tiny_x4()
    cfg.pop("mesh")
    if mesh is not None:
        cfg["mesh"] = mesh
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        bench = json.load(f)
    with pytest.raises(ValueError, match="chips"):
        harness.run(bench, {"name": "bad", "chips": chips}, cfg,
                    traffic.load("cold_fits"), 1, 0.0, False, 0.0,
                    devs=jax.devices(), cache=False)
