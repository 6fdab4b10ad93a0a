"""Mesh surface (``MeshSpec``) + logical-axis sharding rules.

``MeshSpec`` is THE way to hand the estimation system a device mesh: one
frozen description of the 2-D (time x batch) device layout consumed by
:class:`repro.core.estimator.Estimator`, ``serving.TrajectoryEngine`` and
the ``method="distributed"`` solver alike.  ``.build()`` materialises the
``jax.sharding.Mesh``; ``.activate()`` enters :func:`mesh_context` so
ambient consumers (the distributed solver resolving its time axis via
:func:`resolve_time_mesh`, model code using :func:`logical_constraint`)
see the same mesh.  Everywhere a ``mesh=`` argument is accepted, a raw
``Mesh`` keeps working -- :func:`as_mesh` normalises either form.

The rest of this module is the LOGICAL axis-name rules (DP/TP/EP/SP)
with divisibility fallback.  Parameters and activations are annotated
with LOGICAL axis names ("embed", "heads", "ff", "vocab", "experts",
...).  ``choose_pspec`` maps a logical shape to a concrete
``PartitionSpec`` for the active mesh:

* exactly one tensor dimension is model-sharded, picked by walking
  ``MODEL_PRIORITY`` and taking the first logical axis that is present AND
  whose size is divisible by the mesh's model-axis size (llava's 56 q-heads
  do not divide 16 -> falls through to the 128 head_dim; granite's 40
  experts fall through to d_ff);
* the "batch" axis shards over ("pod", "data") (the pod axis is folded into
  data parallelism);
* optimizer-state tensors may additionally shard their largest replicated
  dimension over "data" (ZeRO-1), handled in ``train/optimizer.py``.

``logical_constraint`` applies ``with_sharding_constraint`` when called
under an active mesh context and is a no-op otherwise, so model code is
mesh-agnostic and single-device tests run unchanged.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import threading
from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# priority of logical axes for the single model-sharded dimension
MODEL_PRIORITY: Sequence[str] = (
    "experts", "vocab", "ff", "heads", "kv_heads", "ssm_inner", "ssm_x",
    "ssm_heads", "head", "embed_model",
)

# logical axes that shard over the data (+pod) axes
BATCH_AXES = ("batch",)

# logical axes that may shard over data for sequence parallelism (opt-in)
SEQ_AXES = ("seq_sp",)


class _MeshContext(threading.local):
    def __init__(self):
        self.mesh: Optional[Mesh] = None
        self.data_axes: tuple = ("data",)
        self.model_axis: str = "model"
        self.tp_exclude: frozenset = frozenset()


_CTX = _MeshContext()


@contextlib.contextmanager
def mesh_context(mesh: Mesh, *, batch_axes: tuple = None,
                 tp_exclude=()):
    """Activate logical->physical rules for ``mesh``.

    Meshes with a "pod" axis fold it into the batch sharding.

    ``batch_axes`` overrides the mesh axes used for batch/zero1 sharding
    (e.g. ("pod", "data", "model") for the dp-only policy on small
    models); ``tp_exclude`` removes logical names from the model-sharding
    priority (e.g. everything but "vocab" under dp-only).
    """
    prev = (_CTX.mesh, _CTX.data_axes, _CTX.model_axis, _CTX.tp_exclude)
    _CTX.mesh = mesh
    axis_names = mesh.axis_names
    if batch_axes is not None:
        _CTX.data_axes = tuple(a for a in batch_axes if a in axis_names)
    else:
        _CTX.data_axes = tuple(a for a in ("pod", "data")
                               if a in axis_names)
    _CTX.model_axis = "model" if "model" in axis_names else None
    _CTX.tp_exclude = frozenset(tp_exclude)
    try:
        with mesh:
            yield mesh
    finally:
        (_CTX.mesh, _CTX.data_axes, _CTX.model_axis,
         _CTX.tp_exclude) = prev


def data_parallel_size(mesh: Optional[Mesh] = None) -> int:
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return 1
    return _axis_size(mesh, tuple(_CTX.data_axes)) if _CTX.data_axes else 1


def active_mesh() -> Optional[Mesh]:
    return _CTX.mesh


# ---------------------------------------------------------------------------
# MeshSpec: the one mesh entry point
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class MeshSpec:
    """One declarative description of the 2-D (time x batch) device mesh.

    ``time`` devices shard the TIME axis (the ``method="distributed"``
    associative scan, :func:`repro.core.pscan.sharded_scan`); ``batch``
    devices shard the REQUEST axis (stacked-problem batches,
    ``TrajectoryEngine`` waves).  Either may be 1 -- the axis is still
    named in the mesh, so the same spec works for time-only, batch-only
    and fully 2-D layouts.  Total devices used: ``time * batch`` (the
    first that many of ``jax.devices()`` unless ``.build(devices=...)``
    is given an explicit sequence).

    Pass a ``MeshSpec`` anywhere a ``mesh=`` argument is accepted
    (``Estimator``, ``TrajectoryEngine``) or enter ``.activate()`` to
    make it ambient for mesh-aware code (the distributed solver picks it
    up via :func:`resolve_time_mesh`).
    """

    time: int = 1
    batch: int = 1
    time_axis: str = "time"
    batch_axis: str = "data"

    def __post_init__(self) -> None:
        for field, v in (("time", self.time), ("batch", self.batch)):
            if not isinstance(v, int) or v < 1:
                raise ValueError(
                    f"MeshSpec.{field} must be a positive int, got {v!r}")
        for field, v in (("time_axis", self.time_axis),
                         ("batch_axis", self.batch_axis)):
            if not isinstance(v, str) or not v:
                raise ValueError(
                    f"MeshSpec.{field} must be a non-empty str, got {v!r}")
        if self.time_axis == self.batch_axis:
            raise ValueError(
                f"time_axis and batch_axis must differ, both "
                f"{self.time_axis!r}")

    @property
    def num_devices(self) -> int:
        return self.time * self.batch

    def build(self, devices: Optional[Sequence] = None) -> Mesh:
        """Materialise the ``jax.sharding.Mesh``: ``(time, batch)`` over
        ``(time_axis, batch_axis)`` on the first ``time * batch`` devices."""
        devices = list(jax.devices()) if devices is None else list(devices)
        need = self.num_devices
        if need > len(devices):
            raise ValueError(
                f"MeshSpec needs {need} devices "
                f"({self.time} x {self.batch}), only {len(devices)} "
                f"available")
        arr = np.asarray(devices[:need]).reshape(self.time, self.batch)
        return Mesh(arr, (self.time_axis, self.batch_axis))

    def activate(self):
        """Context manager: build the mesh and enter :func:`mesh_context`
        so ambient consumers (``resolve_time_mesh``,
        ``logical_constraint``) see it."""
        return mesh_context(self.build(), batch_axes=(self.batch_axis,))


def as_mesh(mesh) -> Optional[Mesh]:
    """Normalise the public ``mesh=`` argument: ``None`` | ``Mesh`` |
    ``MeshSpec`` -> ``Optional[Mesh]``."""
    if mesh is None or isinstance(mesh, Mesh):
        return mesh
    if isinstance(mesh, MeshSpec):
        return mesh.build()
    raise TypeError(
        f"mesh must be None, a jax.sharding.Mesh or a MeshSpec, got "
        f"{type(mesh).__name__}")


def mesh_fingerprint(mesh: Optional[Mesh]) -> Optional[Tuple]:
    """A hashable identity for WHICH mesh an executable was compiled
    under: axis names + mesh shape + backend + exact device ids.  Part of
    the executable-cache key so an executable compiled under one mesh is
    never replayed under another (the meshes' collectives differ even
    when argument shapes agree)."""
    if mesh is None:
        return None
    devs = tuple(d.id for d in mesh.devices.flat)
    platform = mesh.devices.flat[0].platform
    return (tuple(mesh.axis_names), tuple(mesh.devices.shape), platform,
            devs)


@functools.lru_cache(maxsize=32)
def _default_time_mesh(time_axis: str, n: int) -> Mesh:
    return Mesh(np.asarray(jax.devices()[:n]), (time_axis,))


def resolve_time_mesh(time_axis: str, *, devices_per_time: Optional[int]
                      = None, mesh: Optional[Mesh] = None) -> Optional[Mesh]:
    """The mesh a time-axis-sharded solve should run under.

    Resolution order: an explicit ``mesh`` carrying ``time_axis``, else
    the ambient :func:`mesh_context` / :meth:`MeshSpec.activate` mesh
    carrying it, else a default 1-D mesh over ``devices_per_time``
    devices (all local devices when ``None``).  Returns ``None`` when
    fewer than 2 time-shards are available -- the caller decides whether
    that falls back to the single-device scan or errors
    (``DistributedOptions.fallback``).
    """
    for candidate in (mesh, _CTX.mesh):
        if candidate is not None and time_axis in candidate.axis_names:
            if (devices_per_time is not None
                    and candidate.shape[time_axis] != devices_per_time):
                raise ValueError(
                    f"devices_per_time={devices_per_time} but the mesh's "
                    f"{time_axis!r} axis has size "
                    f"{candidate.shape[time_axis]}")
            return candidate
    avail = len(jax.devices())
    n = avail if devices_per_time is None else devices_per_time
    if n > avail:
        raise ValueError(
            f"devices_per_time={n} exceeds the {avail} available devices")
    if n < 2:
        return None
    return _default_time_mesh(time_axis, n)


def _axis_size(mesh: Mesh, names) -> int:
    size = 1
    for n in names if isinstance(names, tuple) else (names,):
        size *= mesh.shape[n]
    return size


def choose_pspec(shape: Sequence[int], logical: Sequence[Optional[str]],
                 mesh: Optional[Mesh] = None) -> P:
    """Map logical axes to a PartitionSpec under the active mesh."""
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return P()
    assert len(shape) == len(logical), (shape, logical)
    entries: list = [None] * len(shape)

    # batch / ZeRO-1 axes -> the data axes, with progressive fallback to
    # fewer axes when the dimension does not divide the full product
    # (e.g. batch 256 on a 512-chip dp-only layout).
    for i, name in enumerate(logical):
        if name in BATCH_AXES + ("zero1",) and _CTX.data_axes:
            axes = tuple(_CTX.data_axes)
            while axes:
                if shape[i] % _axis_size(mesh, axes) == 0:
                    entries[i] = axes if len(axes) > 1 else axes[0]
                    break
                axes = axes[1:]

    def used_axes() -> set:
        out = set()
        for e in entries:
            if e is None:
                continue
            out.update(e if isinstance(e, tuple) else (e,))
        return out

    # sequence-parallel axis -> the model axis (megatron-style SP)
    if _CTX.model_axis is not None and _CTX.model_axis not in used_axes():
        msize = mesh.shape[_CTX.model_axis]
        for i, name in enumerate(logical):
            if name in SEQ_AXES and entries[i] is None \
                    and shape[i] % msize == 0:
                entries[i] = _CTX.model_axis
                break

    # one model-sharded dim by priority with divisibility fallback
    if _CTX.model_axis is not None and _CTX.model_axis not in used_axes():
        msize = mesh.shape[_CTX.model_axis]
        for cand in MODEL_PRIORITY:
            if cand in _CTX.tp_exclude:
                continue
            placed = False
            for i, name in enumerate(logical):
                if name == cand and entries[i] is None \
                        and shape[i] % msize == 0 and shape[i] >= msize:
                    entries[i] = _CTX.model_axis
                    placed = True
                    break
            if placed:
                break
    return P(*entries)


def logical_constraint(x, *logical: Optional[str]):
    """with_sharding_constraint by logical names; no-op without a mesh."""
    mesh = _CTX.mesh
    if mesh is None:
        return x
    spec = choose_pspec(x.shape, logical, mesh)
    return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, spec))


def named_sharding(shape, logical, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh
    if mesh is None:
        return None
    return NamedSharding(mesh, choose_pspec(shape, logical, mesh))


def tree_pspecs(axes_tree, shapes_tree, mesh: Optional[Mesh] = None):
    """Map a tree of logical-axes tuples + shapes to PartitionSpecs."""
    mesh = mesh or _CTX.mesh
    return jax.tree_util.tree_map(
        lambda ax, shp: choose_pspec(shp, ax, mesh),
        axes_tree, shapes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(e, (str, type(None))) for e in x),
    )


def tree_shardings(axes_tree, shapes_tree, mesh: Optional[Mesh] = None):
    mesh = mesh or _CTX.mesh
    specs = tree_pspecs(axes_tree, shapes_tree, mesh)
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s), specs,
        is_leaf=lambda x: isinstance(x, P))


def shard_over_batch(fn, mesh: Mesh, batch_axis: str,
                     arg_batched: Sequence[bool]):
    """Wrap a batched function so its leading batch axis spreads over
    ``mesh.shape[batch_axis]`` devices with ``shard_map``.

    ``arg_batched[i]`` marks whether positional arg ``i`` carries the batch
    axis (sharded) or is shared across requests (replicated).  Outputs are
    sharded over the batch axis.  This is the REQUEST-axis decomposition
    used by ``repro.core.batching`` / the ``TrajectoryEngine`` -- the
    complement of the time-axis ``core.pscan.distributed_scan``.
    """
    in_specs = tuple(P(batch_axis) if b else P() for b in arg_batched)
    return jax.shard_map(fn, mesh=mesh, in_specs=in_specs,
                     out_specs=P(batch_axis))
