"""Runtime platform layer: backend selection, precision policy, registry.

Everything backend-shaped lives here so the rest of the framework never
string-compares ``jax.default_backend()``:

* **Backend resolution** — :func:`backend` returns the canonical dispatch
  backend (``"cpu" | "gpu" | "tpu"``); tests can pin it with
  :func:`use_backend`.
* **Kernel registry** — kernel modules register per-backend implementations
  under a name (:func:`register_kernel`) and dispatchers look them up with
  :func:`kernel`; ``*_auto`` dispatch is one table, not N if-statements.
* **Interpret-mode debug flag** — interpret-mode Pallas is an emulation
  tool, not a production path. :func:`resolve_interpret` maps the
  ``interpret=None`` default of every kernel to ``False`` unless the caller
  passed ``interpret=True`` explicitly or the debug flag is set
  (:func:`force_interpret` or ``REPRO_PALLAS_INTERPRET=1``).
* **Precision policy** — :class:`PrecisionPolicy` names the data dtype,
  accumulation dtype, solver-state dtype and the optional fp64 KKT polish;
  presets ``"fp32" | "bf16" | "fp16" | "fp64_polish"`` cover the supported
  combinations.
* **Platform/XLA configuration** — :func:`set_platform` /
  :func:`jax_enable_x64` / :func:`set_cpu_devices` mirror the bayespec
  config idiom, including the GPU async-collective and latency-hiding
  scheduler flags; :func:`enable_compile_cache` places JAX's persistent
  compilation cache for the command-line entry points.
"""
from __future__ import annotations

import contextlib
import dataclasses
import os
import pathlib
from typing import Callable, Iterator

import jax
import jax.numpy as jnp

__all__ = [
    "F32_DOT", "PRECISION_PRESETS", "PrecisionPolicy", "backend",
    "check_x64", "enable_compile_cache", "escalation_ladder",
    "force_interpret", "interpret_default", "jax_enable_x64", "kernel", "kernel_table", "ladder_rounds",
    "precision_name", "register_kernel", "resolve_interpret",
    "resolve_precision", "set_cpu_devices", "set_platform", "use_backend",
    "x64_enabled",
]


# --------------------------------------------------------------- backend --

_BACKEND_OVERRIDE: str | None = None


def backend() -> str:
    """The canonical dispatch backend: ``"cpu"``, ``"gpu"`` or ``"tpu"``."""
    if _BACKEND_OVERRIDE is not None:
        return _BACKEND_OVERRIDE
    b = jax.default_backend()
    return "gpu" if b in ("cuda", "rocm") else b


@contextlib.contextmanager
def use_backend(name: str) -> Iterator[None]:
    """Pin :func:`backend` to ``name`` inside the block (tests only —
    the kernels picked for a pinned backend still *execute* on the real
    devices, so pin a backend whose kernels can run here, or inspect the
    registry without calling through it)."""
    global _BACKEND_OVERRIDE
    prev = _BACKEND_OVERRIDE
    _BACKEND_OVERRIDE = name
    try:
        yield
    finally:
        _BACKEND_OVERRIDE = prev


# ------------------------------------------------------- kernel registry --

_REGISTRY: dict[str, dict[str, Callable]] = {}


def register_kernel(name: str, backend_name: str, fn: Callable) -> Callable:
    """Register ``fn`` as the ``name`` kernel on ``backend_name``.

    ``backend_name`` is one of ``"cpu" | "gpu" | "tpu"`` or ``"default"``
    (the fallback when the current backend has no dedicated entry).
    Re-registration overwrites — last writer wins, so tests can shadow.
    """
    _REGISTRY.setdefault(name, {})[backend_name] = fn
    return fn


def kernel(name: str, backend_name: str | None = None) -> Callable:
    """Resolve the ``name`` kernel for ``backend_name`` (default: current).

    Falls back to the kernel's ``"default"`` entry when the backend has no
    dedicated implementation; raises ``KeyError`` with the known names /
    backends otherwise.
    """
    try:
        table = _REGISTRY[name]
    except KeyError:
        raise KeyError(f"no kernel registered under {name!r}; known: "
                       f"{sorted(_REGISTRY)}") from None
    b = backend_name if backend_name is not None else backend()
    fn = table.get(b, table.get("default"))
    if fn is None:
        raise KeyError(f"kernel {name!r} has no implementation for backend "
                       f"{b!r} and no 'default' entry; has: {sorted(table)}")
    return fn


def kernel_table() -> dict[str, dict[str, Callable]]:
    """A copy of the registry ``{kernel_name: {backend: fn}}`` (for docs,
    tests and the support-matrix generator)."""
    return {name: dict(table) for name, table in _REGISTRY.items()}


# Default bracketing rounds for the ladder projection: backends with a real
# one-pass ladder_stats kernel amortize bracketing rounds over the polish
# loop; on CPU the plain-jnp stats pass is not cheaper than polish steps.
_LADDER_ROUNDS = {"tpu": 2, "gpu": 2}


def ladder_rounds(backend_name: str | None = None) -> int:
    """Default ladder bracketing rounds for ``backend_name`` (current if
    None): 2 where a fused ladder_stats kernel exists, else 0."""
    b = backend_name if backend_name is not None else backend()
    return _LADDER_ROUNDS.get(b, 0)


# ------------------------------------------------- interpret-mode policy --

_FORCE_INTERPRET: bool | None = None  # None -> consult the env var


def interpret_default() -> bool:
    """Whether ``interpret=None`` kernels run interpret-mode Pallas.

    False unless debugging was requested via :func:`force_interpret` or
    ``REPRO_PALLAS_INTERPRET=1`` — production dispatch must never emulate a
    kernel when a compiled implementation (or a plain-jnp fallback chosen by
    the registry) exists.
    """
    if _FORCE_INTERPRET is not None:
        return _FORCE_INTERPRET
    return os.environ.get("REPRO_PALLAS_INTERPRET", "").lower() in (
        "1", "true", "yes")


def resolve_interpret(interpret: bool | None) -> bool:
    """Map a kernel's ``interpret`` argument to the effective flag."""
    return interpret_default() if interpret is None else bool(interpret)


@contextlib.contextmanager
def force_interpret(enable: bool = True) -> Iterator[None]:
    """Force ``interpret=None`` kernels to interpret-mode inside the block
    (debug/test aid; see :func:`interpret_default`)."""
    global _FORCE_INTERPRET
    prev = _FORCE_INTERPRET
    _FORCE_INTERPRET = bool(enable)
    try:
        yield
    finally:
        _FORCE_INTERPRET = prev


# ------------------------------------------------------ precision policy --

# Precision of the solver's own f32 products (the kernels' tiles, inner
# products, the sharded engine's PCG matvecs, column norms). JAX leaves
# an f32 dot's precision to the backend, and on TPU the default may run
# it as one bf16 pass, as a Mosaic kernel's dot does (relative error
# 2.2e-3 measured on v5e), too coarse for tolerances of 1e-4 (outer) and
# 1e-6 (PCG). XLA's own f32 GEMV measured exact either way on v5e, and
# the CPU computes f32 dots in f32, so there it changes nothing.
F32_DOT = jax.lax.Precision.HIGHEST

_DATA_DTYPES = ("bfloat16", "float16", "float32", "float64")
_ACCUM_DTYPES = ("float32", "float64")
_POLISH_DTYPES = ("float64",)
_REDUCED = (jnp.dtype(jnp.bfloat16), jnp.dtype(jnp.float16))


@dataclasses.dataclass(frozen=True)
class PrecisionPolicy:
    """What dtype each stage of the solver runs in.

    ``data``
        Dtype the design/targets are cast to on entry (``None`` keeps
        whatever dtype the caller supplied — no cast, bit-identical to the
        historical behavior).
    ``accum``
        Accumulation dtype of the matvec/gram contractions when the data is
        reduced precision (bf16/fp16). Kernels always accumulate tiles in
        f32; this also sets the dtype the Gram/Cholesky/eigh factors and
        ``A^T b`` are materialized in.
    ``state``
        Dtype of the solver iterates (x, z, t, duals). ``None`` follows the
        (cast) data dtype. The reduced-precision presets pin it to f32 so
        consensus averages and residual norms do not lose bits.
    ``kkt_polish``
        ``"float64"`` runs the closed-form KKT polish loop of
        ``ladder_refine`` in fp64 (requires x64 mode), tightening the
        exact-projection certificate to fp64 ulps. ``None`` polishes in the
        working dtype.
    """

    data: str | None = None
    accum: str = "float32"
    state: str | None = None
    kkt_polish: str | None = None

    def __post_init__(self):
        for name, allowed, optional in (
                ("data", _DATA_DTYPES, True),
                ("accum", _ACCUM_DTYPES, False),
                ("state", _DATA_DTYPES, True),
                ("kkt_polish", _POLISH_DTYPES, True)):
            val = getattr(self, name)
            if val is None and optional:
                continue
            if val not in allowed:
                raise ValueError(f"PrecisionPolicy.{name}={val!r} not in "
                                 f"{allowed}")

    # -- dtype resolution helpers ------------------------------------------
    def cast_data(self, arr: jax.Array) -> jax.Array:
        """``arr`` cast to the policy data dtype (no-op when data=None)."""
        if self.data is None or str(arr.dtype) == self.data:
            return arr
        return arr.astype(self.data)

    def data_dtype(self, incoming) -> jnp.dtype:
        """Effective data dtype given the incoming array dtype."""
        return jnp.dtype(self.data) if self.data else jnp.dtype(incoming)

    def state_dtype(self, data_dtype) -> jnp.dtype:
        """Solver-state dtype given the (already cast) data dtype."""
        return jnp.dtype(self.state) if self.state else jnp.dtype(data_dtype)

    def accum_dtype(self, dtype) -> jnp.dtype:
        """Accumulation/factor dtype for contractions over ``dtype`` data."""
        d = jnp.dtype(dtype)
        return jnp.dtype(self.accum) if d in _REDUCED else d

    @property
    def needs_x64(self) -> bool:
        """True when any stage requests float64 (x64 mode required)."""
        return "float64" in (self.data, self.accum, self.state,
                             self.kkt_polish)


PRECISION_PRESETS: dict[str, PrecisionPolicy] = {
    "fp32": PrecisionPolicy(),
    "bf16": PrecisionPolicy(data="bfloat16", state="float32"),
    "fp16": PrecisionPolicy(data="float16", state="float32"),
    "fp64_polish": PrecisionPolicy(kkt_polish="float64"),
}


def resolve_precision(precision) -> PrecisionPolicy:
    """Resolve a preset name or policy instance to a :class:`PrecisionPolicy`."""
    if isinstance(precision, PrecisionPolicy):
        return precision
    if isinstance(precision, str):
        try:
            return PRECISION_PRESETS[precision]
        except KeyError:
            raise ValueError(
                f"unknown precision preset {precision!r}; known presets: "
                f"{sorted(PRECISION_PRESETS)} (or pass a PrecisionPolicy)"
            ) from None
    raise TypeError("precision must be a preset name or a PrecisionPolicy, "
                    f"got {type(precision).__name__}")


def precision_name(policy: PrecisionPolicy) -> str:
    """Preset name of ``policy`` if it matches one, else a stable custom tag
    (used in driver-cache keys and capability errors)."""
    for name, preset in PRECISION_PRESETS.items():
        if preset == policy:
            return name
    return (f"custom(data={policy.data},accum={policy.accum},"
            f"state={policy.state},kkt_polish={policy.kkt_polish})")


def escalation_ladder(policy) -> list[str]:
    """Preset names strictly more numerically conservative than
    ``policy``, in escalation order — the recovery ladder's precision
    rungs. Reduced-precision data escalates to fp32 first; fp64 polish is
    offered only when x64 mode is actually on (:func:`x64_enabled`), so
    the ladder never constructs a policy :func:`check_x64` would refuse.
    Returns ``[]`` when nothing stricter is available."""
    pol = resolve_precision(policy)
    names: list[str] = []
    if pol.data in ("bfloat16", "float16"):
        names.append("fp32")
        if x64_enabled():
            names.append("fp64_polish")
    elif pol.kkt_polish is None and x64_enabled():
        names.append("fp64_polish")
    return names


def check_x64(policy: PrecisionPolicy) -> None:
    """Raise if ``policy`` requests float64 while jax x64 mode is off."""
    if policy.needs_x64 and not x64_enabled():
        raise ValueError(
            f"precision policy {precision_name(policy)} requests float64 "
            "but jax x64 mode is disabled; call "
            "repro.runtime.jax_enable_x64() (or set JAX_ENABLE_X64=1) first")


# ------------------------------------------------- platform configuration --

# GPU XLA flags (bayespec config idiom): Triton fusions for elementwise
# epilogues, async collectives overlapped with compute by the latency-hiding
# scheduler — the overlap the sharded engine's psum-per-round pattern needs.
_GPU_XLA_FLAGS = (
    "--xla_gpu_enable_triton_softmax_fusion=true",
    "--xla_gpu_triton_gemm_any=True",
    "--xla_gpu_enable_async_collectives=true",
    "--xla_gpu_enable_latency_hiding_scheduler=true",
    "--xla_gpu_enable_highest_priority_async_stream=true",
)


def set_platform(platform: str | None = None) -> None:
    """Pin the jax platform (``"cpu" | "gpu" | "tpu"``); on GPU also set the
    async-collective / latency-hiding XLA flags if absent. Call before any
    jax computation."""
    if platform == "gpu":
        flags = os.environ.get("XLA_FLAGS", "")
        missing = [f for f in _GPU_XLA_FLAGS if f not in flags]
        if missing:
            os.environ["XLA_FLAGS"] = " ".join(filter(None, [flags, *missing]))
    jax.config.update("jax_platform_name", platform)


def jax_enable_x64(use_x64: bool = True) -> None:
    """Toggle double precision globally (needed for fp64 KKT polish)."""
    jax.config.update("jax_enable_x64", bool(use_x64))


def x64_enabled() -> bool:
    """Whether jax x64 mode is currently on."""
    return bool(jax.config.jax_enable_x64)


def set_cpu_devices(n: int) -> None:
    """Emulate ``n`` host devices (test meshes). Call before jax init."""
    flags = os.environ.get("XLA_FLAGS", "")
    flag = f"--xla_force_host_platform_device_count={n}"
    if "xla_force_host_platform_device_count" in flags:
        flags = " ".join(f for f in flags.split()
                         if "xla_force_host_platform_device_count" not in f)
    os.environ["XLA_FLAGS"] = " ".join(filter(None, [flags, flag]))


# The checkout root (src/repro/runtime.py -> <checkout>): a fixed cache
# path, since the path is part of what the cache is found again by.
_DEFAULT_CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is the cache (JAX reads it
    itself; nothing else is set). Otherwise the cache is the fixed
    ``<checkout>/.jax_cache``. Called by the command-line entry points
    (``chip_smoke.py``, ``repro.launch.serve``, ``benchmarks/run.py``),
    never on import and never by tests.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(_DEFAULT_CACHE_DIR))
    return str(_DEFAULT_CACHE_DIR)
