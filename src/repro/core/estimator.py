"""The unified estimation surface: ``Estimator.solve(Problem) -> Solution``.

One composable API replaces the old quintet of entry points
(``map_estimate`` / ``iterated_map`` / ``map_estimate_batched`` /
``map_estimate_ragged`` / ad-hoc engine plumbing):

* :class:`Problem` describes WHAT to solve -- model + time grid +
  measurements (+ optional mask / warm start), in one of three layouts
  built by :meth:`Problem.single`, :meth:`Problem.stacked` (records
  sharing a length) and :meth:`Problem.ragged` (pad-and-bucket over
  unequal lengths).
* :class:`~repro.core.options.SolverOptions` subclasses describe HOW --
  each registered method owns its options dataclass
  (:mod:`repro.core.registry`), so knobs are validated at construction
  and never leak into unrelated signatures.
* :class:`Estimator` binds (model, method, options, mesh) and compiles
  ONE executable per (problem layout, options) key, cached in the
  module-level executable cache (inspect with :func:`cache_stats`).
  ``.solve`` runs it; ``.lower`` returns the ``jax.stages.Lowered`` for
  ahead-of-time compilation.
* :class:`~repro.core.types.Solution` is the result: the MAP trajectory
  and filter information plus diagnostics (Onsager-Machlup cost,
  per-iteration cost trace for nonlinear solves, bucket/padding report
  for ragged solves).

Nonlinear models are solved with the iterated linearisation of section
4.4 (:func:`repro.core.nonlinear.iterated_solve`); wrap the inner method
options in :class:`~repro.core.options.IteratedOptions` to control the
outer loop.
"""
from __future__ import annotations

import collections
import contextlib
import dataclasses
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple, Union

import jax
import jax.numpy as jnp
import numpy as np

from repro import obs

from .nonlinear import iterated_solve
from .options import DistributedOptions, IteratedOptions, SolverOptions
from .padding import bucket_length, next_pow2, pad_record, slice_solution
from .registry import MethodSpec, get_method
from .sde import (
    LinearSDE,
    NonlinearSDE,
    grid_lqt_from_linear,
    om_cost_grid,
)
from .types import BucketInfo, PaddingReport, Solution

Model = Union[LinearSDE, NonlinearSDE]
Records = Sequence[Tuple[np.ndarray, np.ndarray]]


# ---------------------------------------------------------------------------
# Executable cache (absorbed from the old core/batching.py)
# ---------------------------------------------------------------------------


class ExecutableCache:
    """LRU cache of jitted solvers keyed by (model, mesh, method, options,
    problem layout).

    Models are frozen dataclasses holding arrays (unhashable), so the key
    uses ``id(model)``; a strong reference to the model (and mesh) is kept
    in the entry so the id cannot be recycled while cached.  ``maxsize``
    bounds retained executables/models: callers constructing a fresh model
    per request never hit (new id each time) and would otherwise grow the
    cache without bound -- reuse one model object to get executable reuse.

    Hit/miss/eviction counts are kept as plain ints (always, they cost
    nothing) and mirrored into the ``repro.obs`` registry counters
    ``cache.hits`` / ``cache.misses`` / ``cache.evictions`` while obs is
    enabled (aggregated across all cache instances -- the module default
    plus any private ``Estimator(cache=...)`` caches).
    """

    def __init__(self, maxsize: int = 128) -> None:
        self._entries: "collections.OrderedDict[tuple, tuple]" = (
            collections.OrderedDict())
        self._lock = threading.RLock()
        self.maxsize = maxsize
        self.hits = 0
        self.misses = 0
        self.evictions = 0

    def get_entry(self, model: Model, mesh, key_tail: tuple, build):
        """Fetch-or-build; returns ``(fn, fresh)`` where ``fresh`` marks a
        miss (``fn`` was just built and has not executed/compiled yet)."""
        key = (id(model), None if mesh is None else id(mesh)) + key_tail
        with self._lock:
            entry = self._entries.get(key)
            if entry is not None:
                self.hits += 1
                self._entries.move_to_end(key)
                obs.inc("cache.hits")
                return entry[0], False
            self.misses += 1
            obs.inc("cache.misses")
            fn = build()
            self._entries[key] = (fn, model, mesh)
            if len(self._entries) > self.maxsize:
                self._entries.popitem(last=False)
                self.evictions += 1
                obs.inc("cache.evictions")
            return fn, True

    def get(self, model: Model, mesh, key_tail: tuple, build):
        return self.get_entry(model, mesh, key_tail, build)[0]

    def clear(self) -> None:
        with self._lock:
            self._entries.clear()
            self.hits = 0
            self.misses = 0
            self.evictions = 0

    def __len__(self) -> int:
        return len(self._entries)


_CACHE = ExecutableCache()


def cache_stats() -> Dict[str, int]:
    """Default executable-cache counters: one miss per compiled (layout,
    method, options) combination, hits for every reuse, evictions when
    ``maxsize`` forces an LRU drop.

    These are the same counts the obs registry exports as ``cache.*``
    (aggregated over every cache instance) -- ``repro.obs.snapshot()``
    additionally carries the ``cache.compile_seconds`` histogram recorded
    around fresh-executable first runs.  See docs/OBSERVABILITY.md.
    """
    return {"size": len(_CACHE), "hits": _CACHE.hits,
            "misses": _CACHE.misses, "evictions": _CACHE.evictions}


def clear_cache() -> None:
    _CACHE.clear()


# ---------------------------------------------------------------------------
# Problem
# ---------------------------------------------------------------------------


def _check_ny(model: Model, y, where: str = "") -> None:
    """Reject measurements whose trailing dimension does not match the
    model's ``ny`` -- a mismatched ``y`` would otherwise BROADCAST
    silently against ``H x`` in the measurement cost and produce garbage
    estimates instead of an error (skipped when ``R`` is time-varying
    and ``ny`` is not statically known)."""
    ny = model.ny
    if ny is not None and y.shape[-1] != ny:
        raise ValueError(
            f"{where}y has measurement dimension {y.shape[-1]} but the "
            f"model's R is {ny}x{ny} (ny={ny})")


def _check_mask(mask, shape) -> jnp.ndarray:
    mask = jnp.asarray(mask)
    if mask.shape != shape:
        raise ValueError(
            f"measurement_mask must have shape {shape}, got {mask.shape}")
    if jnp.issubdtype(mask.dtype, jnp.bool_) or jnp.issubdtype(
            mask.dtype, jnp.integer):
        mask = mask.astype(jnp.result_type(float))   # 0/1 masks are welcome
    elif not jnp.issubdtype(mask.dtype, jnp.floating):
        raise ValueError(
            f"measurement_mask must be a real 0/1 array (it scales R^-1), "
            f"got dtype {mask.dtype}")
    return mask


def _check_prior(model, prior, batch: Optional[int]):
    """Validate an information-form prior override ``(S0, v0)``.

    ``S0`` is the information matrix (``P0^{-1}``) and ``v0`` the
    information vector (``P0^{-1} m0``) at the first grid point --
    replacing the model's ``(m0, P0)`` boundary without any inversion.
    Shapes: shared ``(nx, nx)``/``(nx,)`` or, for stacked/ragged layouts,
    per-record ``(B, nx, nx)``/``(B, nx)`` (both components must agree).
    """
    if prior is None:
        return None
    try:
        S0, v0 = prior
    except (TypeError, ValueError):
        raise ValueError(
            "prior must be an information-form pair (S0, v0)") from None
    S0, v0 = jnp.asarray(S0), jnp.asarray(v0)
    nx = model.nx
    s_ok, v_ok = {(nx, nx)}, {(nx,)}
    if batch is not None:
        s_ok.add((batch, nx, nx))
        v_ok.add((batch, nx))
    if S0.shape not in s_ok or v0.shape not in v_ok:
        raise ValueError(
            f"prior (S0, v0) must have shapes {sorted(s_ok)} / "
            f"{sorted(v_ok)}, got {S0.shape} / {v0.shape}")
    if (S0.ndim == 3) != (v0.ndim == 2):
        raise ValueError(
            f"prior S0 and v0 must be both shared or both per-record, "
            f"got shapes {S0.shape} / {v0.shape}")
    return (S0, v0)


def _check_x_init(model, x_init, N: int, batch: Optional[int]):
    if x_init is None:
        return None
    if not isinstance(model, NonlinearSDE):
        raise ValueError(
            "x_init is only meaningful for NonlinearSDE problems (it warm-"
            "starts the iterated linearisation)")
    x_init = jnp.asarray(x_init)
    nx = model.nx
    shared = {(nx,), (N + 1, nx)}
    if batch is None:
        if x_init.shape not in shared:
            raise ValueError(
                f"x_init must be ({nx},) or ({N + 1}, {nx}), "
                f"got {x_init.shape}")
    else:
        batched = {(batch, nx), (batch, N + 1, nx)}
        if x_init.shape not in shared | batched:
            raise ValueError(
                f"x_init must be shared ({nx},)/({N + 1}, {nx}) or "
                f"per-record ({batch}, {nx})/({batch}, {N + 1}, {nx}), "
                f"got {x_init.shape}")
    return x_init


@dataclasses.dataclass(frozen=True, eq=False)
class Problem:
    """One estimation workload: model + data (+ optional mask/warm start).

    Build via :meth:`single`, :meth:`stacked` or :meth:`ragged` -- the
    constructors validate shapes/dtypes up front so errors surface at
    construction, not inside a trace.  ``kind`` records the layout; for
    ragged problems ``ts``/``y`` (and a per-record ``x_init``) are tuples
    of per-record arrays.
    """

    model: Model
    ts: Any
    y: Any
    measurement_mask: Optional[jnp.ndarray] = None
    x_init: Any = None
    prior: Any = None
    kind: str = "single"
    bucket_sizes: Optional[Tuple[int, ...]] = None
    pad_batch: bool = True

    # -- constructors -------------------------------------------------------

    @classmethod
    def single(cls, model: Model, ts, y, *, measurement_mask=None,
               x_init=None, prior=None) -> "Problem":
        """One record: ``ts`` ``(N+1,)``, ``y`` ``(N, ny)``.

        ``prior`` ``(S0, v0)``: information-form initial boundary
        (``P0^{-1}``, ``P0^{-1} m0``) replacing the model's ``(m0, P0)``
        -- fixed-lag window solves pass the forward-filter information at
        the window's left edge here (see docs/STREAMING.md)."""
        ts = jnp.asarray(ts)
        y = jnp.asarray(y)
        if y.ndim != 2 or y.shape[0] < 1:
            raise ValueError(f"y must be (N, ny) with N >= 1, got {y.shape}")
        N = y.shape[0]
        if ts.shape != (N + 1,):
            raise ValueError(f"ts must be (N+1,) = {(N + 1,)}, got {ts.shape}")
        _check_ny(model, y)
        if measurement_mask is not None:
            measurement_mask = _check_mask(measurement_mask, (N,))
        x_init = _check_x_init(model, x_init, N, None)
        prior = _check_prior(model, prior, None)
        return cls(model, ts, y, measurement_mask, x_init, prior,
                   kind="single")

    @classmethod
    def stacked(cls, model: Model, ts, ys, *, measurement_mask=None,
                x_init=None, prior=None) -> "Problem":
        """Stacked records ``ys`` ``(B, N, ny)`` sharing the interval
        count; ``ts`` shared ``(N+1,)`` or per-record ``(B, N+1)``.

        ``x_init`` (nonlinear models): shared ``(nx,)`` / ``(N+1, nx)``
        or per-record ``(B, nx)`` / ``(B, N+1, nx)``.  If ``B == N+1``
        makes a rank-2 shape ambiguous, the per-record reading wins --
        tile to ``(B, N+1, nx)`` to force a shared trajectory.

        ``prior`` ``(S0, v0)``: shared ``(nx, nx)``/``(nx,)`` or
        per-record ``(B, nx, nx)``/``(B, nx)`` information-form initial
        boundaries (see :meth:`single`)."""
        ys = jnp.asarray(ys)
        if ys.ndim != 3:
            raise ValueError(f"ys must be (B, N, ny), got shape {ys.shape}")
        ts = jnp.asarray(ts)
        B, N = ys.shape[0], ys.shape[1]
        if ts.shape[-1] != N + 1:
            raise ValueError(
                f"ts has {ts.shape[-1]} points but ys has {N} intervals "
                f"(need N+1 = {N + 1})")
        if ts.ndim == 2 and ts.shape[0] != B:
            raise ValueError(f"ts batch {ts.shape[0]} != ys batch {B}")
        if ts.ndim not in (1, 2):
            raise ValueError(f"ts must be (N+1,) or (B, N+1), got {ts.shape}")
        _check_ny(model, ys)
        if measurement_mask is not None:
            measurement_mask = _check_mask(measurement_mask, (B, N))
        x_init = _check_x_init(model, x_init, N, B)
        prior = _check_prior(model, prior, B)
        return cls(model, ts, ys, measurement_mask, x_init, prior,
                   kind="stacked")

    @classmethod
    def ragged(cls, model: Model, records: Records, *, x_init=None,
               prior=None, bucket_sizes: Optional[Sequence[int]] = None,
               pad_batch: bool = True) -> "Problem":
        """Records of unequal length: ``records`` is a sequence of
        ``(ts_i, y_i)`` pairs with ``ts_i`` ``(N_i+1,)``, ``y_i``
        ``(N_i, ny)``.  ``x_init`` may be one shared ``(nx,)`` point or a
        sequence of per-record ``(nx,)`` points.  Solved by pad-and-bucket
        (see :mod:`repro.core.padding`); the returned solutions carry a
        :class:`~repro.core.types.PaddingReport`.
        """
        records = tuple(records)
        if not records:
            raise ValueError("records must be non-empty")
        ts_all, y_all = [], []
        for i, (ts_i, y_i) in enumerate(records):
            ts_i = np.asarray(ts_i)
            y_i = np.asarray(y_i)
            if y_i.ndim != 2 or y_i.shape[0] < 1:
                raise ValueError(
                    f"record {i}: y must be (N, ny) with N >= 1, "
                    f"got {y_i.shape}")
            if ts_i.shape != (y_i.shape[0] + 1,):
                raise ValueError(
                    f"record {i}: ts must be (N+1,) = "
                    f"{(y_i.shape[0] + 1,)}, got {ts_i.shape}")
            _check_ny(model, y_i, where=f"record {i}: ")
            ts_all.append(ts_i)
            y_all.append(y_i)
        if x_init is not None:
            if not isinstance(model, NonlinearSDE):
                raise ValueError(
                    "x_init is only meaningful for NonlinearSDE problems")
            x_init = np.asarray(x_init)
            nx = model.nx
            if x_init.shape not in {(nx,), (len(records), nx)}:
                raise ValueError(
                    f"ragged x_init must be ({nx},) shared or "
                    f"({len(records)}, {nx}) per-record points, "
                    f"got {x_init.shape}")
        prior = _check_prior(model, prior, len(records))
        return cls(model, tuple(ts_all), tuple(y_all), None, x_init, prior,
                   kind="ragged",
                   bucket_sizes=None if bucket_sizes is None
                   else tuple(bucket_sizes),
                   pad_batch=pad_batch)

    # -- layout helpers -----------------------------------------------------

    @property
    def num_records(self) -> int:
        if self.kind == "single":
            return 1
        if self.kind == "stacked":
            return self.y.shape[0]
        return len(self.y)

    @property
    def lengths(self) -> Tuple[int, ...]:
        """Interval count per record."""
        if self.kind == "single":
            return (self.y.shape[0],)
        if self.kind == "stacked":
            return (self.y.shape[1],) * self.y.shape[0]
        return tuple(y_i.shape[0] for y_i in self.y)


# ---------------------------------------------------------------------------
# Estimator
# ---------------------------------------------------------------------------


def _solve_arrays(model: Model, spec: MethodSpec, options, ts, y, mask,
                  x_init, prior=None, diagnostics: bool = True) -> Solution:
    """Solve ONE record; the traced core every executable is built from.

    ``diagnostics=False`` skips the Onsager-Machlup cost evaluation (a
    pinv/eval pass over the grid per solve -- small next to the solve, but
    pure overhead for callers that never read ``Solution.cost``).
    """
    if isinstance(model, NonlinearSDE):
        inner = options.inner
        sol, trace, steps = iterated_solve(
            model, ts, y, lambda grid: spec.solver(grid, inner),
            iterations=options.iterations,
            divergence_correction=options.divergence_correction,
            x_init=x_init, measurement_mask=mask, prior=prior,
            track_costs=diagnostics,
            linearization=options.linearization)
        if not diagnostics:
            return Solution(x=sol.x, S=sol.S, v=sol.v, cov=sol.cov)
        return Solution(x=sol.x, S=sol.S, v=sol.v, cov=sol.cov,
                        cost=trace[-1], cost_trace=trace, step_norms=steps)
    grid = grid_lqt_from_linear(model, ts, y, measurement_mask=mask,
                                prior=prior)
    sol = spec.solver(grid, options)
    cost = None
    if diagnostics:
        # In float32 (the chip) a time-invariant Q gets one pseudo-inverse,
        # taken on the host: a per-point SVD compiles for minutes on the
        # TPU at long horizons.  Float64 runs only on the CPU, where that
        # constant beside core.linalg's LAPACK forms gave a NaN trajectory
        # from parallel_two_filter, right again with XLA:CPU's
        # concurrency-optimized scheduler turned off (PERF.md, open
        # questions), so it keeps the per-point form.
        Qpinv = None
        if not callable(model.Q) and sol.x.dtype != jnp.float64:
            Qpinv = np.linalg.pinv(np.asarray(model.Q)).astype(sol.x.dtype)
        cost = om_cost_grid(grid, sol.x, Qpinv)
    return Solution(x=sol.x, S=sol.S, v=sol.v, cov=sol.cov, cost=cost)


def legacy_options(model: Model, method: str, *, nsub=None, mode=None,
                   iterations=None, divergence_correction=None):
    """Map the old kwarg soup onto the method's options dataclass
    (deprecation-shim support; fields a method does not declare are
    dropped, mirroring how the old dispatch ignored them)."""
    spec = get_method(method)
    inner = spec.options_cls.from_legacy(nsub=nsub, mode=mode)
    if isinstance(model, NonlinearSDE):
        outer = {k: v for k, v in
                 dict(iterations=iterations,
                      divergence_correction=divergence_correction).items()
                 if v is not None}
        return IteratedOptions(inner=inner, **outer)
    return inner


class Estimator:
    """Compiled MAP estimation for one model + method + options.

    Args:
      model: shared :class:`LinearSDE` / :class:`NonlinearSDE`; problems
        passed to :meth:`solve` must be built with this model object (the
        executable cache is anchored on it).
      method: registered method name (see
        :func:`repro.core.registry.method_names`).  Backends are fully
        interchangeable here -- e.g. ``"parallel_kernel"`` (the Pallas
        lane-major scan, ``docs/KERNELS.md``) runs through the same
        executable cache, vmap/shard_map batching and AOT ``lower`` path
        as the jnp methods.  Iterated nonlinear methods
        (``"sigma_point"``) are NOT grid solvers: they require a
        ``NonlinearSDE`` and run the iterated linearisation loop around
        the linear method named by their options' ``inner_method``.
      options: instance of the method's options class
        (:class:`~repro.core.options.SolverOptions` subclass); for
        nonlinear models either that (outer loop defaults) or an
        :class:`~repro.core.options.IteratedOptions` wrapping it.  ``None``
        means all defaults.
      mesh: optional ``jax.sharding.Mesh`` OR
        :class:`repro.distributed.MeshSpec` (the one mesh entry point --
        normalised via :func:`repro.distributed.as_mesh`).  Stacked
        batches are sharded over ``mesh.shape[batch_axis]`` devices;
        ``method="distributed"`` additionally shards the time axis over
        the mesh axis named by its options (an ambient
        :meth:`MeshSpec.activate` / ``mesh_context`` mesh is picked up
        when this argument is ``None``).  A mesh/device fingerprint is
        part of the executable-cache key, so an executable compiled under
        one mesh is never replayed under another.
      diagnostics: compute ``Solution.cost`` / ``cost_trace`` (default).
        ``False`` skips the Onsager-Machlup evaluations -- use for hot
        serving paths that never read them.
      cache: optional private :class:`ExecutableCache` (default: the
        module-level cache shared by all estimators).
    """

    def __init__(self, model: Model, *, method: str = "parallel_rts",
                 options=None, mesh=None, batch_axis: str = "data",
                 diagnostics: bool = True,
                 cache: Optional[ExecutableCache] = None):
        from repro.distributed.sharding import as_mesh

        self._spec = get_method(method)
        self.model = model
        self.method = method
        self.options = self._resolve_options(options)
        # The spec that actually solves each (linearised) grid problem:
        # iterated nonlinear methods (spec.nonlinear, e.g. "sigma_point")
        # delegate to their options' inner_method; every other method IS
        # the grid solver.
        self._grid_spec = (get_method(self.options.inner_method)
                           if self._spec.nonlinear else self._spec)
        self.mesh = as_mesh(mesh)
        self.batch_axis = batch_axis
        self.diagnostics = diagnostics
        self._cache = _CACHE if cache is None else cache
        self._distributed = issubclass(self._grid_spec.options_cls,
                                       DistributedOptions)

    def _resolve_options(self, options):
        cls = self._spec.options_cls
        if self._spec.nonlinear:
            # Iterated nonlinear method (e.g. "sigma_point"): the options
            # ARE the outer-loop options; the grid solver is named by
            # options.inner_method and its options ride in options.inner.
            if not isinstance(self.model, NonlinearSDE):
                raise TypeError(
                    f"method {self.method!r} is an iterated nonlinear "
                    f"method and needs a NonlinearSDE model, got "
                    f"{type(self.model).__name__}")
            if options is None:
                options = cls()
            elif isinstance(options, SolverOptions):
                options = cls(inner=options)
            elif not isinstance(options, cls):
                raise TypeError(
                    f"options for method {self.method!r} must be "
                    f"{cls.__name__} (or a bare inner-method SolverOptions),"
                    f" got {type(options).__name__}")
            inner_spec = get_method(options.inner_method)
            if inner_spec.nonlinear:
                raise ValueError(
                    f"inner_method {options.inner_method!r} is itself an "
                    f"iterated nonlinear method; it must name a linear grid "
                    f"solver (e.g. 'parallel_rts', 'sequential_rts')")
            inner = (options.inner if options.inner is not None
                     else inner_spec.options_cls())
            if not isinstance(inner, inner_spec.options_cls):
                raise TypeError(
                    f"{cls.__name__}.inner for inner_method "
                    f"{options.inner_method!r} must be "
                    f"{inner_spec.options_cls.__name__}, got "
                    f"{type(inner).__name__}")
            return options.replace(inner=inner)
        if isinstance(self.model, NonlinearSDE):
            if options is None:
                options = IteratedOptions()
            elif isinstance(options, cls):
                options = IteratedOptions(inner=options)
            elif not isinstance(options, IteratedOptions):
                raise TypeError(
                    f"options for nonlinear method {self.method!r} must be "
                    f"{cls.__name__} or IteratedOptions, got "
                    f"{type(options).__name__}")
            if type(options) is not IteratedOptions:
                raise TypeError(
                    f"{type(options).__name__} belongs to an iterated "
                    f"nonlinear method, not method={self.method!r}; use "
                    f"the method it was registered with (e.g. "
                    f"method='sigma_point') or plain IteratedOptions")
            inner = options.inner if options.inner is not None else cls()
            if not isinstance(inner, cls):
                raise TypeError(
                    f"IteratedOptions.inner for method {self.method!r} must "
                    f"be {cls.__name__}, got {type(inner).__name__}")
            return options.replace(inner=inner)
        if isinstance(options, IteratedOptions):
            raise TypeError(
                "IteratedOptions is for NonlinearSDE models; linear models "
                f"take {cls.__name__}")
        if options is None:
            options = cls()
        if not isinstance(options, cls):
            raise TypeError(
                f"options for method {self.method!r} must be "
                f"{cls.__name__}, got {type(options).__name__}")
        return options

    @property
    def block_size(self) -> int:
        """Grid-length multiple required by the method (``nsub`` for
        parallel methods, 1 otherwise) -- the bucketing unit."""
        o = self.options
        if isinstance(o, IteratedOptions):
            o = o.inner
        return getattr(o, "nsub", 1)

    # -- mesh plumbing ------------------------------------------------------

    def _method_options(self):
        """The method-level options (unwrapping ``IteratedOptions``)."""
        o = self.options
        return o.inner if isinstance(o, IteratedOptions) else o

    def _resolved_mesh(self):
        """The mesh THIS solve will actually run under.

        Non-distributed methods use ``self.mesh`` as-is.  The distributed
        method resolves exactly like its solver will at trace time
        (explicit mesh, else ambient context, else default time-only
        mesh; ``None`` = single-device fallback), so the executable-cache
        fingerprint and the traced collectives always agree.
        """
        if not self._distributed:
            return self.mesh
        from repro.distributed.sharding import resolve_time_mesh

        o = self._method_options()
        return resolve_time_mesh(o.time_axis,
                                 devices_per_time=o.devices_per_time,
                                 mesh=self.mesh)

    def _batch_spmd_axis(self, mesh) -> Optional[str]:
        """The mesh axis a distributed stacked batch shards over: the
        first of ``options.batch_axes`` present on the mesh (so the same
        options work on time-only and 2-D meshes)."""
        if mesh is None:
            return None
        o = self._method_options()
        for a in o.batch_axes:
            if a in mesh.axis_names and a != o.time_axis:
                return a
        return None

    def _batch_shard_size(self, mesh) -> int:
        """Devices the stacked batch axis spreads over (1 = unsharded)."""
        if mesh is None:
            return 1
        if self._distributed:
            ax = self._batch_spmd_axis(mesh)
            return mesh.shape[ax] if ax is not None else 1
        if self.batch_axis in mesh.axis_names:
            return mesh.shape[self.batch_axis]
        return 1

    def _mesh_scope(self):
        """Context activating ``self.mesh`` around traced calls, so the
        distributed solver resolves the SAME mesh the cache key was
        fingerprinted with (jit traces lazily, inside the first call)."""
        if self.mesh is None:
            return contextlib.nullcontext()
        from repro.distributed.sharding import mesh_context

        return mesh_context(self.mesh, batch_axes=(self.batch_axis,))

    # -- executable construction -------------------------------------------

    def _check_model(self, problem: Problem) -> None:
        if problem.model is not self.model:
            raise ValueError(
                "problem.model is not this Estimator's model object; build "
                "the Problem with the same model instance (executables are "
                "cached per model object)")

    def _prepare(self, problem: Problem):
        """Fetch/compile the executable for this problem's layout; returns
        ``(jitted_fn, args, fresh)`` -- ``fresh`` marks a cache miss (the
        executable compiles on its first run)."""
        self._check_model(problem)
        from repro.distributed.sharding import mesh_fingerprint

        ts, y = problem.ts, problem.y
        mask, x_init = problem.measurement_mask, problem.x_init
        stacked = problem.kind == "stacked"
        resolved = self._resolved_mesh()
        if stacked:
            axis = self._batch_shard_size(resolved)
            if axis > 1 and y.shape[0] % axis:
                raise ValueError(
                    f"batch {y.shape[0]} not divisible by mesh batch axis "
                    f"size {axis}")

        args: List[Any] = [ts, y]
        axes: List[Optional[int]] = [0 if (stacked and ts.ndim == 2) else None,
                                     0 if stacked else None]
        if mask is not None:
            args.append(mask)
            axes.append(0 if stacked else None)
        if x_init is not None:
            args.append(x_init)
            if not stacked:
                axes.append(None)
            else:
                # (nx,) / (N+1, nx) are shared, (B, nx) / (B, N+1, nx)
                # per-record; in the ambiguous B == N+1 rank-2 case the
                # per-record reading wins (tile to (B, N+1, nx) to force a
                # shared trajectory).
                B = y.shape[0]
                shared = x_init.ndim == 1 or (
                    x_init.ndim == 2 and x_init.shape[0] != B)
                axes.append(None if shared else 0)
        prior = problem.prior
        if prior is not None:
            per_rec = stacked and prior[0].ndim == 3
            args.extend(prior)
            axes.extend([0 if per_rec else None] * 2)

        has_mask, has_xinit = mask is not None, x_init is not None
        has_prior = prior is not None
        # mesh_fingerprint of the RESOLVED mesh: an executable traced
        # under one mesh (its collectives bake in axis names, shard
        # counts and device ids) is never replayed under another, even
        # when the Estimator itself holds mesh=None and the mesh arrives
        # ambiently.
        key_tail = (
            self.method, self.options, problem.kind, self.batch_axis,
            mesh_fingerprint(resolved),
            has_mask, has_xinit, has_prior, self.diagnostics,
            tuple((a.shape, str(a.dtype)) for a in args),
            tuple(axes))
        model, spec, options = self.model, self._grid_spec, self.options
        spmd_axis = self._batch_spmd_axis(resolved) if (
            stacked and self._distributed) else None

        def build():
            def solve_one(*call_args):
                it = iter(call_args)
                t, yy = next(it), next(it)
                m = next(it) if has_mask else None
                xi = next(it) if has_xinit else None
                pr = (next(it), next(it)) if has_prior else None
                return _solve_arrays(model, spec, options, t, yy, m, xi,
                                     prior=pr,
                                     diagnostics=self.diagnostics)

            fn = solve_one
            if stacked:
                if self._distributed:
                    # vmap composes with the solver's inner shard_map;
                    # spmd_axis_name lands the batch dim on the mesh's
                    # batch axis for 2-D (time x batch) layouts.  (A
                    # shard_map wrapper would nest shard_maps, which jax
                    # does not support.)
                    if spmd_axis is not None and resolved.shape[
                            spmd_axis] > 1:
                        fn = jax.vmap(fn, in_axes=tuple(axes),
                                      spmd_axis_name=spmd_axis)
                    else:
                        fn = jax.vmap(fn, in_axes=tuple(axes))
                else:
                    fn = jax.vmap(fn, in_axes=tuple(axes))
                    if (self.mesh is not None
                            and self.batch_axis in self.mesh.axis_names):
                        from repro.distributed.sharding import (
                            shard_over_batch)
                        fn = shard_over_batch(
                            fn, self.mesh, self.batch_axis,
                            tuple(ax == 0 for ax in axes))
            return jax.jit(fn)

        fn, fresh = self._cache.get_entry(model, self.mesh, key_tail, build)
        return fn, tuple(args), fresh

    # -- public surface -----------------------------------------------------

    def solve(self, problem: Problem):
        """Solve a :class:`Problem`.

        Returns a :class:`~repro.core.types.Solution` (single/stacked
        layouts; stacked fields carry a leading batch axis) or a list of
        per-record ``Solution``\\ s in submission order (ragged layout,
        each carrying the shared
        :class:`~repro.core.types.PaddingReport`).

        While ``repro.obs`` is enabled (and ``diagnostics`` is on -- the
        hot-serving opt-out also silences instrumentation) the solve is
        measured: phase spans ``estimator.solve.{prepare,compile,execute,
        host_transfer}``, the ``cache.compile_seconds`` histogram for
        fresh executables, and nonlinear iteration metrics.  The measured
        path blocks on the result (spans time real work, not dispatch);
        outputs are bit-exact either way.
        """
        if problem.kind == "ragged":
            return self._solve_ragged(problem)
        if not (self.diagnostics and obs.enabled()):
            # hot path: no obs objects touched, fully async dispatch
            with self._mesh_scope():
                fn, args, _ = self._prepare(problem)
                return fn(*args)
        with obs.trace_span("estimator.solve"):
            with obs.trace_span("estimator.solve.prepare"):
                fn, args, fresh = self._prepare(problem)
            phase = ("estimator.solve.compile" if fresh
                     else "estimator.solve.execute")
            t0 = time.perf_counter()
            with obs.trace_span(phase, xla=True), self._mesh_scope():
                out = fn(*args)
                jax.block_until_ready(out)
            if fresh:
                obs.record("cache.compile_seconds",
                           time.perf_counter() - t0)
            with obs.trace_span("estimator.solve.host_transfer"):
                self._record_solution_metrics(out)
        return out

    def _record_solution_metrics(self, sol: Solution) -> None:
        """Host-side readout of per-solve diagnostics into the registry
        (concrete device arrays only -- never called from traced code)."""
        obs.inc("estimator.solves")
        if isinstance(self.options, IteratedOptions):
            lin = self.options.linearization
            obs.inc(f"linearize.{lin.obs_name}.solves")
            obs.set_gauge("linearize.sigma_points",
                          lin.num_points(self.model.nx))
        if sol.cost is not None:
            obs.record("estimator.final_cost", np.mean(np.asarray(sol.cost)))
        if sol.cost_trace is not None:
            trace = np.asarray(sol.cost_trace)
            obs.set_gauge("nonlinear.iterations", trace.shape[-1])
            obs.record("nonlinear.cost_decrease",
                       float(np.mean(trace[..., 0] - trace[..., -1])))
        if sol.step_norms is not None:
            steps = np.asarray(sol.step_norms)
            obs.record("nonlinear.final_step_norm",
                       float(np.mean(steps[..., -1])))

    def lower(self, problem: Problem) -> "jax.stages.Lowered":
        """Ahead-of-time path: the ``jax.stages.Lowered`` for this
        problem's layout (``.compile()`` it, then call with the problem's
        arrays).  Ragged problems compose several stacked executables and
        cannot be lowered as one program -- lower per-bucket stacked
        problems instead."""
        if problem.kind == "ragged":
            raise ValueError(
                "lower() supports single/stacked problems; a ragged solve "
                "composes one executable per bucket")
        with obs.trace_span("estimator.lower"), self._mesh_scope():
            fn, args, _ = self._prepare(problem)
            return fn.lower(*args)

    # -- ragged pad-and-bucket ---------------------------------------------

    def _solve_ragged(self, problem: Problem) -> List[Solution]:
        self._check_model(problem)
        nsub = self.block_size
        lengths = problem.lengths
        buckets: Dict[int, List[int]] = {}
        for i, N_i in enumerate(lengths):
            n_pad = bucket_length(N_i, nsub, problem.bucket_sizes)
            buckets.setdefault(n_pad, []).append(i)

        x_init = problem.x_init
        per_record_xi = x_init is not None and x_init.ndim == 2
        prior = problem.prior
        per_record_prior = prior is not None and prior[0].ndim == 3

        out: List[Optional[Solution]] = [None] * len(lengths)
        infos: List[BucketInfo] = []
        for n_pad, idxs in sorted(buckets.items()):
            padded = [pad_record(problem.ts[i], problem.y[i], n_pad)
                      for i in idxs]
            B = len(padded)
            B_pad = next_pow2(B) if problem.pad_batch else B
            axis = self._batch_shard_size(self._resolved_mesh())
            if axis > 1:
                B_pad = -(-B_pad // axis) * axis
            rows = padded + [padded[0]] * (B_pad - B)   # recycle row 0
            ts_b = jnp.asarray(np.stack([r[0] for r in rows]))
            ys_b = jnp.asarray(np.stack([r[1] for r in rows]))
            mask_b = jnp.asarray(np.stack([r[2] for r in rows]))
            xi_b = None
            if per_record_xi:
                xi_rows = [x_init[i] for i in idxs]
                xi_b = jnp.asarray(np.stack(
                    xi_rows + [xi_rows[0]] * (B_pad - B)))
            elif x_init is not None:
                xi_b = jnp.asarray(x_init)
            pr_b = prior
            if per_record_prior:
                recycle = [idxs[0]] * (B_pad - B)
                pr_b = (jnp.stack([prior[0][i] for i in idxs + recycle]),
                        jnp.stack([prior[1][i] for i in idxs + recycle]))
            sub = Problem.stacked(self.model, ts_b, ys_b,
                                  measurement_mask=mask_b, x_init=xi_b,
                                  prior=pr_b)
            sol = self.solve(sub)
            infos.append(BucketInfo(n_pad=n_pad, records=B, batch=B_pad))
            for row, i in enumerate(idxs):
                out[i] = slice_solution(sol, row, lengths[i])

        report = PaddingReport(lengths=tuple(lengths), buckets=tuple(infos))
        if self.diagnostics and obs.enabled():
            obs.inc("padding.records", report.records)
            obs.inc("padding.real_intervals", report.real_intervals)
            obs.inc("padding.solved_intervals", report.solved_intervals)
            obs.set_gauge("padding.interval_utilisation",
                          report.interval_utilisation)
            obs.set_gauge("padding.row_utilisation", report.row_utilisation)
            obs.set_gauge("padding.waste", 1.0 - report.interval_utilisation)
        return [dataclasses.replace(s, padding=report) for s in out]
