"""RasterAPI v2: typed raster pytrees, the backend registry, and static keys.

The rasterization pipeline is a first-class object here instead of a pile of
positional arrays + backend-specific kwargs:

* :class:`RasterInputs` — everything *dynamic* a rasterizer consumes: the
  projected per-Gaussian 2D attributes plus the per-tile
  :class:`~repro.core.sorting.FragmentLists`.  A plain pytree of arrays, so
  it vmaps/scans/dons like any other bundle; a leading view axis on every
  leaf means "batched multi-view".
* :class:`RasterPlan` — everything *static* about how to execute: tile grid,
  chunk size, fragment capacity, backend name, Pallas interpret mode
  (derived from the platform unless given) — plus the one
  dynamic execution input, an optional carried
  :class:`~repro.core.schedule.TileSchedule`.  Registered as a pytree whose
  only child is the schedule, so a plan can ride a ``lax.scan`` carry while
  its static fields key compilation caches.
* **backend registry** — rasterizer implementations self-register under a
  name via :func:`register_backend`; :func:`get_backend` resolves a plan's
  backend string.  New kernel variants plug in without touching
  ``core/render.py`` (the built-ins live in ``repro/kernels/ops.py``).
* :func:`static_fingerprint` — a generic hashable fingerprint of the static
  leaves of a config object (dataclasses, NamedTuples, primitives,
  containers).  The SLAM engine derives its compile-cache key from this, so
  adding a config field can never silently serve stale executables again.
"""

from __future__ import annotations

import dataclasses
from typing import Callable, NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.projection import ProjectedGaussians
from repro.core.schedule import TileSchedule
from repro.core.sorting import FragmentLists, TileGrid
from repro.kernels import resolve_interpret


class RasterInputs(NamedTuple):
    """Dynamic rasterizer operands (projected splat attrs + fragment lists).

    Every leaf may carry a leading view axis ``B`` (``mu2d`` becomes
    ``(B, N, 2)``, ``frags.idx`` becomes ``(B, T, K)`` …) to request batched
    multi-view rasterization; backends must then return ``(B, H, W, …)``
    outputs bit-identical to rasterizing each view separately.
    """

    mu2d: jnp.ndarray      # (N, 2) pixel-space means
    conic: jnp.ndarray     # (N, 3) inverse-covariance upper triangle
    color: jnp.ndarray     # (N, 3)
    opacity: jnp.ndarray   # (N,)
    depth: jnp.ndarray     # (N,)
    frags: FragmentLists   # per-tile fragment lists (idx/count index plumbing)

    @classmethod
    def from_projection(cls, proj: ProjectedGaussians,
                        frags: FragmentLists) -> "RasterInputs":
        return cls(mu2d=proj.mu2d, conic=proj.conic, color=proj.color,
                   opacity=proj.opacity, depth=proj.depth, frags=frags)

    @property
    def views(self) -> Optional[int]:
        """Leading view-axis length, or ``None`` for a single view."""
        return self.mu2d.shape[0] if self.mu2d.ndim == 3 else None


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass(frozen=True)
class RasterPlan:
    """How to rasterize: static execution parameters + an optional carried
    WSU schedule (the only dynamic leaf).

    The static fields flatten into pytree aux data, so jit/scan treat two
    plans differing in, say, ``backend`` as different computations, while a
    carried ``sched`` flows through scan carries like any array bundle.
    """

    grid: TileGrid
    backend: str = "ref"        # registry name, see register_backend()
    chunk: int = 16             # kernel chunk size (C)
    capacity: int = 128         # fragments per tile (K)
    interpret: Optional[bool] = None  # Pallas interpret mode; None = CPU only
    sched_bucket: int = 1       # WSU trip-count bucketing (schedule backend)
    sched: Optional[TileSchedule] = None  # carried schedule (dynamic)

    def __post_init__(self):
        object.__setattr__(self, "interpret",
                           resolve_interpret(self.interpret))

    def tree_flatten(self):
        return (self.sched,), self.static_leaves

    @classmethod
    def tree_unflatten(cls, aux, children):
        _, grid, backend, chunk, capacity, interpret, sched_bucket = aux
        return cls(grid=grid, backend=backend, chunk=chunk, capacity=capacity,
                   interpret=interpret, sched_bucket=sched_bucket,
                   sched=children[0])

    @property
    def static_leaves(self) -> tuple:
        """Hashable tuple of every compile-relevant (non-array) field."""
        return ("RasterPlan", self.grid, self.backend, self.chunk,
                self.capacity, self.interpret, self.sched_bucket)

    @property
    def max_trips(self) -> int:
        return self.capacity // self.chunk

    def with_sched(self, sched: Optional[TileSchedule]) -> "RasterPlan":
        return dataclasses.replace(self, sched=sched)


# ---------------------------------------------------------------------------
# backend registry
# ---------------------------------------------------------------------------

# name -> fn(inputs: RasterInputs, plan: RasterPlan) -> (color_pm, depth_pm,
# final_t), each (H, W, …) or (B, H, W, …) when inputs carry a view axis.
_BACKENDS: dict[str, Callable] = {}


def register_backend(name: str) -> Callable[[Callable], Callable]:
    """Decorator: register a rasterizer implementation under ``name``.

    The function receives ``(inputs, plan)`` and must honor batched inputs
    (leading view axis) bit-identically to a per-view loop.  Re-registering
    a name replaces the previous implementation (last one wins), which is
    what you want when hot-swapping an experimental kernel in a notebook.
    """

    def deco(fn: Callable) -> Callable:
        _BACKENDS[name] = fn
        return fn

    return deco


def registered_backends() -> tuple[str, ...]:
    """Names of all registered rasterizer backends (built-ins included)."""
    from repro.kernels import ops  # noqa: F401  (registers the built-ins)

    return tuple(sorted(_BACKENDS))


def get_backend(name: str) -> Callable:
    try:
        return _BACKENDS[name]
    except KeyError:
        raise ValueError(
            f"unknown raster backend {name!r}; registered backends: "
            f"{', '.join(registered_backends())}"
        ) from None


# ---------------------------------------------------------------------------
# deprecation plumbing (shared by the ops.rasterize / render shims)
# ---------------------------------------------------------------------------

_WARNED_KEYS: set = set()


def warn_once(key: str, msg: str, stacklevel: int = 3) -> None:
    """Emit ``msg`` as a DeprecationWarning the first time ``key`` is seen
    (one mechanism for every legacy-signature shim; tests reset by
    discarding the key from ``_WARNED_KEYS``)."""
    import warnings

    if key not in _WARNED_KEYS:
        _WARNED_KEYS.add(key)
        warnings.warn(msg, DeprecationWarning, stacklevel=stacklevel)


# ---------------------------------------------------------------------------
# static fingerprints (auto-derived compile keys)
# ---------------------------------------------------------------------------


def static_fingerprint(obj) -> tuple | str | bytes | int | float | bool | None:
    """Hashable fingerprint of every static leaf of a config-like object.

    Recurses through dataclasses, NamedTuples, tuples/lists/dicts and
    primitives, tagging each level with type and field names so two configs
    differing in *any* field — present or future — fingerprint differently.
    Objects exposing ``static_leaves`` (e.g. :class:`RasterPlan`) contribute
    exactly those.  Array leaves are rejected loudly: arrays are runtime
    operands, not compile keys.
    """
    if isinstance(obj, RasterPlan) or (
        not isinstance(obj, type) and hasattr(obj, "static_leaves")
        and not isinstance(obj, (jnp.ndarray,))
    ):
        return tuple(obj.static_leaves)
    if dataclasses.is_dataclass(obj) and not isinstance(obj, type):
        return (type(obj).__name__,) + tuple(
            (f.name, static_fingerprint(getattr(obj, f.name)))
            for f in dataclasses.fields(obj)
        )
    if isinstance(obj, tuple) and hasattr(obj, "_fields"):  # NamedTuple
        return (type(obj).__name__,) + tuple(
            (n, static_fingerprint(getattr(obj, n))) for n in obj._fields
        )
    if isinstance(obj, (tuple, list)):
        return (type(obj).__name__,) + tuple(static_fingerprint(x) for x in obj)
    if isinstance(obj, dict):
        return ("dict",) + tuple(
            (k, static_fingerprint(v)) for k, v in sorted(obj.items())
        )
    if obj is None or isinstance(obj, (str, bytes, int, float, bool, complex)):
        return obj
    if callable(obj):
        # id() keeps two distinct closures with the same qualname from
        # colliding (a collision would silently serve stale executables —
        # the exact bug class this function kills); the worst case of
        # including it is a spurious cache miss, never a stale hit.
        return ("callable", getattr(obj, "__module__", ""),
                getattr(obj, "__qualname__", repr(obj)), id(obj))
    raise TypeError(
        f"{type(obj).__name__} is not a static leaf (arrays and other "
        "runtime values cannot key a compilation cache)"
    )
