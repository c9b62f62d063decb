"""The benchmark's generator: one seed gives one data set, on one device
or with each node's rows made on its own device."""
import json
import os
import subprocess
import sys

import jax.numpy as jnp
import numpy as np

import data

CFG = dict(n_nodes=4, m_per_node=96, n_features=40, sparsity_level=0.9,
           noise=1e-2, loss="squared")
CHIP = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_same_seed_same_data_and_seeds_apart():
    A1, A2 = data.make_design(5, CFG), data.make_design(5, CFG)
    assert np.array_equal(np.asarray(A1), np.asarray(A2))
    assert not np.array_equal(np.asarray(A1),
                              np.asarray(data.make_design(5 + 2 ** 32, CFG)))
    norms = np.linalg.norm(np.asarray(A1).reshape(-1, 40), axis=0)
    np.testing.assert_allclose(norms, 1.0, rtol=1e-5)
    b0, x0 = data.make_response(A1, 5, 0, CFG)
    b1, x1 = data.make_response(A1, 5, 1, CFG)
    assert int(jnp.sum(x0 != 0)) == data.kappa(CFG) == 4
    assert float(jnp.min(jnp.abs(x0[x0 != 0]))) >= 1.0
    assert not np.array_equal(np.asarray(b0), np.asarray(b1))
    lab, _ = data.make_response(A1, 5, 0, dict(CFG, loss="logistic"))
    assert set(np.unique(np.asarray(lab))) == {-1.0, 1.0}


SHARDED = """
import json, sys
import numpy as np
import jax
from jax.sharding import NamedSharding, PartitionSpec as P, AxisType
sys.path.insert(0, {chip!r})
import data
cfg = json.loads({cfg!r})
mesh = jax.make_mesh((4, 1), ("nodes", "feat"),
                     axis_types=(AxisType.Auto,) * 2)
sh = NamedSharding(mesh, P("nodes"))
A4 = data.make_design(2 ** 31 + 9, cfg, sh)
assert len(A4.sharding.device_set) == 4
assert A4.addressable_shards[0].data.shape == (1, 96, 40)
A1 = jax.device_put(data.make_design(2 ** 31 + 9, cfg), jax.devices()[0])
out = dict(design=float(np.max(np.abs(np.asarray(A4) - np.asarray(A1)))))
for loss in ("squared", "logistic"):
    c = dict(cfg, loss=loss)
    b4, _ = data.make_response(A4, 2 ** 31 + 9, 3, c)
    b1, _ = data.make_response(A1, 2 ** 31 + 9, 3, c)
    out[loss] = float(np.max(np.abs(np.asarray(b4) - np.asarray(b1))))
print(json.dumps(out))
"""


def test_sharded_generation_equals_one_device():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = SHARDED.format(chip=CHIP, cfg=json.dumps(CFG))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=300)
    assert res.returncode == 0, res.stderr[-2000:]
    gaps = json.loads(res.stdout.strip().splitlines()[-1])
    # the draws are the same; only the order of the column-norm sums may
    # differ, by a rounding of a unit-norm column's entries
    assert gaps["design"] <= 1e-6, gaps
    assert gaps["squared"] <= 1e-5, gaps
    assert gaps["logistic"] == 0.0, gaps
