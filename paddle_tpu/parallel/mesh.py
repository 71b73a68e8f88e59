"""Global device mesh management.

The reference builds one NCCL communicator per topology axis slice
(ref: /root/reference/python/paddle/distributed/fleet/base/topology.py:140-156
HybridCommunicateGroup). The TPU-native equivalent is ONE
jax.sharding.Mesh whose named axes are the parallelism axes; every
"communication group" is a mesh axis name, and collectives are XLA ops that
ride ICI/DCN (SURVEY.md §5 'Distributed communication backend').

Axis names: 'dp' (data), 'pp' (pipeline), 'sharding' (ZeRO), 'mp'
(tensor/model), 'sep' (sequence/context parallel — absent in the reference,
first-class here).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec

AXIS_ORDER = ("dp", "pp", "sharding", "sep", "mp")

_global_mesh: Optional[Mesh] = None


def build_mesh(dp=1, pp=1, sharding=1, sep=1, mp=1, devices=None) -> Mesh:
    """Create and install the global mesh. Innermost axis ('mp') maps to the
    fastest ICI links, mirroring the reference's topology order
    [data, pipe, sharding, model] (topology.py:54) with 'model' innermost."""
    global _global_mesh
    devices = list(devices if devices is not None else jax.devices())
    sizes = {"dp": dp, "pp": pp, "sharding": sharding, "sep": sep, "mp": mp}
    total = int(np.prod(list(sizes.values())))
    if total > len(devices):
        raise ValueError(
            f"mesh needs {total} devices, only {len(devices)} available")
    if total < len(devices) and dp == -1:
        sizes["dp"] = len(devices) // (pp * sharding * sep * mp)
        total = len(devices)
    arr = np.array(devices[:total]).reshape(
        [sizes[a] for a in AXIS_ORDER])
    _global_mesh = Mesh(arr, AXIS_ORDER)
    return _global_mesh


def get_mesh() -> Mesh:
    global _global_mesh
    if _global_mesh is None:
        # default: pure data parallel over all local devices
        build_mesh(dp=len(jax.devices()))
    return _global_mesh


def set_mesh(mesh: Mesh):
    global _global_mesh
    _global_mesh = mesh


def mesh_axis_size(axis: str) -> int:
    m = get_mesh()
    return m.shape[axis] if axis in m.shape else 1


def serving_shard_devices(mp: int):
    """Device list for ``mp`` tensor-parallel SERVING shards — the
    reuse point between the training mesh and the sharded paged
    serving stack (inference/serving.py ShardedServingCore +
    inference/paged_cache.py sharded pools). Resolution order:

      1. the installed global mesh's 'mp' axis when it is at least
         ``mp`` wide (the dp=0/pp=0/... row — innermost axis, fastest
         ICI links, exactly the communicator the training side uses);
      2. ``jax.devices()`` when there are at least ``mp`` of them
         (e.g. ``XLA_FLAGS=--xla_force_host_platform_device_count=N``
         CPU meshes with no mesh installed yet);
      3. otherwise, OFF the TPU only, the available devices CYCLED —
         LOGICAL shards: several shards share one physical device.
         Numerics and the collective schedule are identical to a real
         mesh (the per-shard executables don't know their neighbors),
         only the placement is degenerate — this is how the tier-1
         in-process bit-identity tests run mp=2 on a single-device CI
         host. On a TPU, asking for more shards than chips is an
         error: logical shards there would quietly serve the
         host-staged loop while the caller believes the pool is spread.
    """
    from ..framework.device import on_tpu
    mp = int(mp)
    if mp < 1:
        raise ValueError(f"mp must be >= 1, got {mp}")
    devs = list(jax.devices())
    m = _global_mesh
    if m is not None and m.shape.get("mp", 1) >= mp:
        # the mp axis is last in AXIS_ORDER: reshape to [-1, mp_size]
        # and take the first row's leading mp devices
        arr = np.asarray(m.devices).reshape(-1, m.shape["mp"])
        return [arr[0, i] for i in range(mp)]
    if mp > len(devs) and on_tpu():
        raise ValueError(
            f"mp={mp} serving shards need {mp} TPU devices, only "
            f"{len(devs)} present")
    return [devs[i % len(devs)] for i in range(mp)]


def serving_mesh(mp: int, devices=None) -> Optional[Mesh]:
    """One-axis ``Mesh(("mp",))`` over the serving shard devices —
    the mesh the compiled sharded step (inference/compiled_step.py)
    jits its shard_map program over. Returns None when the resolved
    devices are not ``mp`` DISTINCT physical devices: jax refuses a
    Mesh with repeats, and logical same-device shards belong on the
    host-staged legacy path anyway (nothing to compile across)."""
    mp = int(mp)
    if mp < 1:
        raise ValueError(f"mp must be >= 1, got {mp}")
    devs = list(devices) if devices is not None \
        else serving_shard_devices(mp)
    devs = devs[:mp]
    if len(devs) < mp or len(set(devs)) < mp:
        return None
    return Mesh(np.array(devs), ("mp",))


def named_sharding(*spec) -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec(*spec))


def replicated_sharding() -> NamedSharding:
    return NamedSharding(get_mesh(), PartitionSpec())


def shard_tensor_data(data, spec: PartitionSpec):
    """Place a jax array on the global mesh with the given PartitionSpec."""
    return jax.device_put(data, NamedSharding(get_mesh(), spec))


_constraint_warned: set = set()


def _current_mesh():
    """The mesh to annotate against: inside a shard_map/use_mesh trace this
    is the context's AbstractMesh (whose axis_types mark manual axes);
    otherwise the concrete global mesh."""
    am = jax.sharding.get_abstract_mesh()
    if am.axis_names:
        return am
    return get_mesh()


def _manual_axes(m):
    return {n for n, t in zip(m.axis_names, m.axis_types)
            if t == jax.sharding.AxisType.Manual}


def constraint(x, *spec):
    """with_sharding_constraint that is a no-op outside jit.

    Inside a partial-manual shard_map region (e.g. the pp/sep pipeline),
    entries naming a manual axis are dropped — those dims are structurally
    local there — and the sharding is built on the context's AbstractMesh so
    axis types agree. A fully dropped constraint is loud (warned once per
    spec): silently discarding sharding constraints can turn an SPMD
    program into a replicated one."""
    m = _current_mesh()
    manual = _manual_axes(m)
    if manual:
        def filt(s):
            if isinstance(s, (tuple, list)):
                kept = tuple(a for a in s if a not in manual)
                return kept if kept else None
            return None if s in manual else s
        spec = tuple(filt(s) for s in spec)
    try:
        return jax.lax.with_sharding_constraint(
            x, NamedSharding(m, PartitionSpec(*spec)))
    except Exception as e:  # outside jit, or axis not in the current mesh
        key = spec
        if key not in _constraint_warned:
            _constraint_warned.add(key)
            import warnings
            warnings.warn(
                f"sharding constraint {spec} dropped ({type(e).__name__}: {e})"
                " — expected outside jit; inside jit this means the program "
                "is NOT sharded as annotated", stacklevel=2)
        return x


def inside_spmd_region(axis: str) -> bool:
    try:
        jax.lax.axis_index(axis)
        return True
    except NameError:    # "unbound axis name": not under a shard_map on it
        return False
