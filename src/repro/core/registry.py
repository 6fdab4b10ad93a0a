"""Method registry: one dispatch table for every MAP solver backend.

Each entry is a :class:`MethodSpec` pairing the solver callable with the
:class:`~repro.core.options.SolverOptions` dataclass it owns, so
method-specific knobs (``nsub``, ``block0_fill``, ...) live with the
solver instead of widening every public signature.  New backends (e.g. a
kernel-backed combine, a distributed-scan variant) plug in with
:func:`register_method` without touching any call site:

    registry.register_method("my_method", solver, MyOptions)

where ``solver(grid: GridLQT, options: MyOptions) -> MAPSolution``.  The
legacy ``solver(grid, nsub, mode)`` signature (pre-options registrations)
is still accepted when ``options_cls`` is omitted; it is adapted to the
canonical form and assigned :class:`~repro.core.options.ParallelOptions`.
"""
from __future__ import annotations

import contextlib
from typing import Callable, Dict, NamedTuple, Optional, Tuple, Type

from .options import (
    DistributedOptions,
    IteratedOptions,
    KernelOptions,
    ParallelOptions,
    SequentialOptions,
    SigmaPointOptions,
    SolverOptions,
    TwoFilterOptions,
)
from .parallel import parallel_rts, parallel_two_filter
from .sequential import sequential_rts, sequential_two_filter
from .types import GridLQT, MAPSolution

Solver = Callable[[GridLQT, SolverOptions], MAPSolution]


class MethodSpec(NamedTuple):
    """A registered solver backend: name + canonical solver + its options."""

    name: str
    solver: Solver
    options_cls: Type[SolverOptions]

    def default_options(self) -> SolverOptions:
        return self.options_cls()

    @property
    def nonlinear(self) -> bool:
        """True for methods whose options are the iterated-linearisation
        layer (``IteratedOptions`` subclasses): they require a nonlinear
        model and delegate each linearised subproblem to an inner linear
        method instead of acting as a grid solver themselves."""
        return issubclass(self.options_cls, IteratedOptions)


_METHODS: Dict[str, MethodSpec] = {}


def register_method(
    name: str,
    solver: Callable,
    options_cls: Optional[Type[SolverOptions]] = None,
    *,
    overwrite: bool = False,
) -> None:
    """Register a solver backend under ``name``.

    ``solver`` must accept ``(grid, options)`` -- with ``options`` an
    instance of ``options_cls`` -- and return a
    :class:`~repro.core.types.MAPSolution`.  Omitting ``options_cls``
    registers a legacy ``(grid, nsub, mode)`` solver, adapted in place.
    """
    if options_cls is None:
        legacy = solver

        def solver(grid, options, _legacy=legacy):  # noqa: F811
            return _legacy(grid, getattr(options, "nsub", 1), options.mode)

        options_cls = ParallelOptions
    elif not (isinstance(options_cls, type)
              and issubclass(options_cls, (SolverOptions, IteratedOptions))):
        raise TypeError(
            f"options_cls must be a SolverOptions subclass (or an "
            f"IteratedOptions subclass for nonlinear methods), got "
            f"{options_cls!r}")
    if name in _METHODS and not overwrite:
        raise ValueError(f"method {name!r} already registered")
    _METHODS[name] = MethodSpec(name, solver, options_cls)


def get_method(name: str) -> MethodSpec:
    try:
        return _METHODS[name]
    except KeyError:
        raise ValueError(
            f"method must be one of {method_names()}, got {name!r}"
        ) from None


def get_solver(name: str) -> Callable:
    """Back-compat accessor: a ``(grid, nsub, mode)`` adapter around the
    registered solver (fields the method's options do not declare are
    dropped)."""
    spec = get_method(name)

    def solver(grid, nsub, mode):
        return spec.solver(grid,
                           spec.options_cls.from_legacy(nsub=nsub, mode=mode))

    return solver


def method_names() -> Tuple[str, ...]:
    return tuple(_METHODS)


def _parallel_kernel_solver(grid: GridLQT, o: KernelOptions) -> MAPSolution:
    """RTS smoother with the backward scan run by the Pallas lane-major
    combine kernel (one layout round-trip for the whole multi-level scan).

    The kernel package is imported lazily so ``repro.core`` never depends
    on ``repro.kernels`` at import time (the kernels import core types).
    """
    from repro.kernels.lqt_combine.ops import kernel_suffix_scan

    interpret = o.resolve_interpret()

    def suffix(elems):
        return kernel_suffix_scan(elems, block_b=o.block_size,
                                  interpret=interpret, precision=o.precision)

    return parallel_rts(grid, o.nsub, o.mode, suffix_scan_fn=suffix)


def _distributed_solver(grid: GridLQT, o: DistributedOptions) -> MAPSolution:
    """RTS smoother with both global scans sharded over a named time axis
    (:func:`repro.core.pscan.sharded_scan`): local Blelloch scan per shard,
    one all-gather of the P per-shard carries, redundant carry scan, local
    fix-up -- span O(log(T/P) + P).

    The mesh is resolved at TRACE time: an explicit/ambient mesh carrying
    ``options.time_axis`` (see :func:`repro.distributed.resolve_time_mesh`)
    wins, else a default time-only mesh over ``devices_per_time`` (or all
    visible) devices is built.  With fewer than 2 time shards the solver
    degrades to the single-device parallel scan (``fallback="auto"``) or
    raises (``fallback="error"``).
    """
    from repro.distributed.sharding import (
        active_mesh, mesh_context, resolve_time_mesh)

    from . import pscan
    from .combine import affine_combine, lqt_combine

    mesh = resolve_time_mesh(
        o.time_axis, devices_per_time=o.devices_per_time)
    if mesh is None:
        if o.fallback == "error":
            raise RuntimeError(
                f"method='distributed' needs >= 2 devices on mesh axis "
                f"{o.time_axis!r} (fallback='error'); pass "
                f"fallback='auto' to degrade to the single-device scan")
        return parallel_rts(grid, o.nsub, o.mode)

    carry_dtype = o.resolve_carry_dtype()

    def suffix(elems):
        return pscan.sharded_scan(
            lqt_combine, elems, mesh=mesh, axis_name=o.time_axis,
            reverse=True, carry_dtype=carry_dtype)

    def prefix(elems):
        return pscan.sharded_scan(
            affine_combine, elems, mesh=mesh, axis_name=o.time_axis,
            carry_dtype=carry_dtype)

    # The mesh becomes the ambient one (where none is), so the stages
    # outside the sharded scans see that the program spans several devices.
    with (mesh_context(mesh) if active_mesh() is None
          else contextlib.nullcontext()):
        return parallel_rts(grid, o.nsub, o.mode,
                            suffix_scan_fn=suffix, prefix_scan_fn=prefix)


register_method(
    "parallel_rts",
    lambda grid, o: parallel_rts(grid, o.nsub, o.mode),
    ParallelOptions)
register_method("parallel_kernel", _parallel_kernel_solver, KernelOptions)
register_method("distributed", _distributed_solver, DistributedOptions)
register_method(
    "parallel_two_filter",
    lambda grid, o: parallel_two_filter(
        grid, o.nsub, o.mode, jitter=o.jitter,
        block0_fill=o.block0_fill, tf_fill=o.tf_fill),
    TwoFilterOptions)
def _sigma_point_solver(grid: GridLQT, o: SigmaPointOptions) -> MAPSolution:
    """``sigma_point`` is not a grid solver: the Estimator resolves its
    ``inner_method`` and runs the iterated loop around THAT solver.  Only
    a direct ``spec.solver(grid, options)`` call -- which would silently
    skip the linearisation loop -- lands here."""
    raise TypeError(
        "method='sigma_point' is an iterated nonlinear method, not a grid "
        "solver; use Estimator(model, method='sigma_point').solve(problem) "
        "with a NonlinearSDE model")


register_method("sigma_point", _sigma_point_solver, SigmaPointOptions)
register_method(
    "sequential_rts",
    lambda grid, o: sequential_rts(grid, o.mode),
    SequentialOptions)
register_method(
    "sequential_two_filter",
    lambda grid, o: sequential_two_filter(grid, o.mode),
    SequentialOptions)
