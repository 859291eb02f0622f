"""Device-mesh helpers: the TPU topology surface that replaces the
reference's AffinityManager device enumeration (SURVEY.md §2.9) and carries
the sharding layout for data/model parallelism over ICI/DCN.

r12 (mesh-sharded generation): :func:`make_mesh` builds named multi-axis
meshes with CLEAR validation errors (axis arity, device budget vs
``jax.device_count()``) instead of the opaque numpy reshape failure the
old path produced, :func:`generation_mesh` is the canonical 2-axis
``(data, tp)`` serving mesh, and :func:`validate_decode_mesh` checks the
decode divisibility contract (attention heads over ``tp``, cache slots
over ``data``) up front, where the message can name the knob to change.
"""

from __future__ import annotations

from typing import Optional, Sequence, Tuple

import jax
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

#: canonical serving-mesh axis names: batch/cache-slots shard over
#: ``data``, attention heads / projection columns over ``tp``
DATA_AXIS = "data"
TP_AXIS = "tp"


def make_mesh(n_devices: Optional[int] = None,
              axis_names: Sequence[str] = ("data",),
              shape: Optional[Tuple[int, ...]] = None,
              devices: Optional[Sequence] = None) -> Mesh:
    """Build a Mesh over the first n_devices (default: all). For multi-axis
    meshes pass shape, e.g. shape=(4, 2), axis_names=("data", "tp").

    Fails with a clear error when the requested axes cannot be laid out
    on the available devices (the old path let numpy raise an opaque
    "cannot reshape array" from deep inside jax dispatch)."""
    devs = list(jax.devices()) if devices is None else list(devices)
    if n_devices is not None:
        if n_devices > len(devs):
            raise ValueError(
                f"make_mesh(n_devices={n_devices}) but only {len(devs)} "
                f"device(s) are available (jax.device_count()="
                f"{jax.device_count()}); on CPU force virtual devices "
                "with XLA_FLAGS=--xla_force_host_platform_device_count=N")
        devs = devs[:n_devices]
    axis_names = tuple(axis_names)
    if shape is None:
        if len(axis_names) != 1:
            raise ValueError(
                f"make_mesh: {len(axis_names)} axis names {axis_names} "
                "but no shape — pass shape=(...), one size per axis "
                "(e.g. shape=(2, 2) for axes ('data', 'tp'))")
        shape = (len(devs),)
    shape = tuple(int(s) for s in shape)
    if len(shape) != len(axis_names):
        raise ValueError(
            f"make_mesh: shape {shape} has {len(shape)} dims but "
            f"axis_names {axis_names} has {len(axis_names)} — one size "
            "per named axis")
    if any(s < 1 for s in shape):
        raise ValueError(f"make_mesh: shape {shape} — every axis size "
                         "must be >= 1")
    need = int(np.prod(shape))
    if need > len(devs):
        raise ValueError(
            f"mesh shape {shape} ({dict(zip(axis_names, shape))}) needs "
            f"{need} devices but only {len(devs)} are available "
            f"(jax.device_count()={jax.device_count()}); shrink an axis "
            "or, on CPU, force virtual devices with "
            "XLA_FLAGS=--xla_force_host_platform_device_count=N")
    arr = np.array(devs[:need]).reshape(shape)
    return Mesh(arr, axis_names)


def generation_mesh(data: int = 1, tp: int = 1,
                    devices: Optional[Sequence] = None) -> Mesh:
    """The canonical serving mesh: ``(data, tp)`` with cache slots/batch
    sharded over ``data`` and attention heads over ``tp``."""
    return make_mesh(axis_names=(DATA_AXIS, TP_AXIS),
                     shape=(int(data), int(tp)), devices=devices)


def parse_mesh_shape(text: str) -> Tuple[int, int]:
    """``"2x1"`` → ``(2, 1)``; a bare ``"2"`` means ``(2, 1)`` (data-
    parallel decode). The bench/soak CLIs share this grammar."""
    s = str(text).strip().lower()
    parts = s.split("x")
    if len(parts) == 1:
        parts = [parts[0], "1"]
    if len(parts) != 2:
        raise ValueError(f"mesh shape '{text}' — expected 'DATAxTP' "
                         "(e.g. '2x1') or a bare device count")
    try:
        data, tp = int(parts[0]), int(parts[1])
    except ValueError:
        raise ValueError(f"mesh shape '{text}' — sizes must be integers "
                         "('DATAxTP', e.g. '1x2')") from None
    if data < 1 or tp < 1:
        raise ValueError(f"mesh shape '{text}' — axis sizes must be >= 1")
    return data, tp


def mesh_axis_sizes(mesh: Mesh, data_axis: str = DATA_AXIS,
                    tp_axis: str = TP_AXIS) -> Tuple[int, int]:
    """(data size, tp size); an absent axis counts as size 1, so 1-axis
    data meshes and 2-axis serving meshes share one code path."""
    return (int(mesh.shape.get(data_axis, 1)),
            int(mesh.shape.get(tp_axis, 1)))


def validate_decode_mesh(mesh: Mesh, num_heads: Optional[int] = None,
                         num_slots: Optional[int] = None,
                         data_axis: str = DATA_AXIS,
                         tp_axis: str = TP_AXIS) -> None:
    """Decode divisibility contract, checked BEFORE any device dispatch:
    attention heads shard over ``tp`` (the [S, H/g, T, g·Dh] cache
    splits on its head-group axis), cache slots over ``data`` (the cache
    splits on S). A violation
    raises with the exact knob to change instead of an XLA sharding
    error at the first prefill. Pass only the quantities the caller
    owns (the decoder checks heads, the engine checks slots)."""
    data, tp = mesh_axis_sizes(mesh, data_axis, tp_axis)
    if num_heads is not None and tp > 1 and int(num_heads) % tp:
        raise ValueError(
            f"num_heads {num_heads} is not divisible by the '{tp_axis}' "
            f"axis size {tp} — the KV cache shards heads over "
            f"'{tp_axis}'; use a head count divisible by {tp} or a "
            "smaller tp axis")
    if num_slots is not None and data > 1 and int(num_slots) % data:
        raise ValueError(
            f"num_slots {num_slots} is not divisible by the "
            f"'{data_axis}' axis size {data} — cache slots shard over "
            f"'{data_axis}'; use a slot count divisible by {data} or a "
            "smaller data axis")


def mesh_tag(mesh: Optional[Mesh]) -> str:
    """Short attribution tag for a mesh ("2x1" for a (data=2, tp=1)
    serving mesh; generic meshes join every axis size). The compile
    auditor needs per-mesh jit names: two meshes lowering the same
    function with the same shapes would otherwise read as one function
    compiling the SAME signature twice — a false blown-cache signal."""
    if mesh is None:
        return ""
    return "x".join(str(int(mesh.shape[a])) for a in mesh.axis_names)


def replicated(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharded(mesh: Mesh, axis: str = "data") -> NamedSharding:
    return NamedSharding(mesh, P(axis))


def shard_batch_spec(ndim: int, axis: str = "data") -> P:
    """PartitionSpec sharding dim 0 (batch) over ``axis``."""
    return P(axis, *([None] * (ndim - 1)))
