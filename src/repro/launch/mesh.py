"""Production meshes and logical-axis maps.

``make_production_mesh`` is a FUNCTION (not a module-level constant) so that
importing this module never touches jax device state — the dry-run process
must set XLA_FLAGS before the first jax call.
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType, Mesh

from repro.sharding import MeshCtx
from repro.sharding.rules import ShardingRules


def auto_mesh(shape, axes, devices=None) -> Mesh:
    """``jax.make_mesh`` with Auto axes. Its default is Explicit axes,
    which put shardings into array types; the layouts here are written
    as ``with_sharding_constraint`` hints (``repro.sharding.ctx``), which
    need Auto axes."""
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(shape),
                         devices=devices)


def make_production_mesh(*, multi_pod: bool = False) -> Mesh:
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return auto_mesh(shape, axes, devices=jax.devices()[: _prod(shape)])


def _prod(t):
    out = 1
    for v in t:
        out *= v
    return out


def logical_axes(mesh: Mesh, profile: str = "tp_sp") -> dict:
    """Logical -> physical axis map for MeshCtx.

    Profiles (§Perf hillclimbing):
      tp_sp — baseline 2D Megatron layout: batch over ("pod","data"), TP
              over "model", sequence-parallel residuals over "model"
              ("sp"), ZeRO weights over "data".
      fsdp  — ZeRO-3-dominant layout: batch AND weights sharded over every
              axis; no tensor parallelism (tp/sp unmapped -> replicated
              dims), experts stay expert-parallel over "model" ("ep").
    """
    multi = "pod" in mesh.axis_names
    all_axes = ("pod", "data", "model") if multi else ("data", "model")
    dp2 = ("pod", "data") if multi else "data"
    if profile == "fsdp":
        # dp_moe: the expert capacity buffer keeps batch on the data axes
        # only, freeing the model axis for expert parallelism (the
        # dp->(dp_moe, ep) reshard is the MoE all-to-all).
        return {"dp": all_axes, "tp": None, "sp": None,
                "ep": "model", "dp_moe": dp2, "fsdp": all_axes}
    if profile == "fsdp_sp":
        # multi-pod variant: when global batch < device count, shard
        # activations along sequence over "model" (SP) instead of trying
        # to stretch dp across it; weights stay ZeRO-sharded everywhere.
        return {"dp": dp2, "tp": None, "sp": "model",
                "ep": "model", "dp_moe": dp2, "fsdp": all_axes}
    if profile == "fsdp_ep":
        # MoE variant: batch over data only (so EP keeps the model axis),
        # non-expert weights ZeRO-sharded over BOTH axes, no TP/SP.
        # NOTE: recorded hillclimb dead-end — replicates dense compute
        # over the model axis (see EXPERIMENTS.md §Perf).
        return {"dp": dp2, "tp": None, "sp": None,
                "ep": "model", "dp_moe": dp2, "fsdp": all_axes}
    return {"dp": dp2,
            "tp": "model",
            "sp": "model",
            "ep": "model",
            "dp_moe": dp2,
            "fsdp": "data"}


PROFILES = ("tp_sp", "fsdp", "fsdp_sp", "fsdp_ep")


def make_ctx(mesh: Mesh, profile: str = "tp_sp") -> MeshCtx:
    return MeshCtx(mesh, logical_axes(mesh, profile))


def make_rules(mesh: Mesh, profile: str = "tp_sp") -> ShardingRules:
    multi = "pod" in mesh.axis_names
    axes = ("pod", "data", "model") if multi else ("data", "model")
    if profile in ("fsdp", "fsdp_sp", "fsdp_ep"):
        return ShardingRules(fsdp=axes, tp=None, ep="model")
    return ShardingRules(fsdp="data", tp="model", ep="model")


def make_solver_mesh(*, multi_pod: bool = False) -> Mesh:
    """Bi-cADMM mesh: the paper's N nodes = ("pod","data"), M GPUs = model."""
    return make_production_mesh(multi_pod=multi_pod)
