"""Collective communication API (ref: /root/reference/python/paddle/
distributed/communication/ — all_reduce.py, all_gather.py, ...; C++ kernels
paddle/fluid/distributed/collective/process_group_nccl.cc:174).

Two execution contexts:
1. Inside a shard_map per-device region (how fleet layers / pipeline
   schedules use them): lowered to lax.psum / all_gather / ppermute /
   all_to_all over the group's mesh axis — XLA collectives on ICI.
2. Eager on global arrays: the array is interpreted as carrying per-rank
   values along the group axis (sharded) and the collective is run as a
   jitted shard_map over the global mesh. Replicated inputs are already
   "synchronized" in the GSPMD world, so sum-reduce of a replicated tensor
   is the identity (the reference's allreduce-of-synced-grads pattern).
"""
from __future__ import annotations

from typing import List, Optional

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec

from ...framework.op import unwrap, wrap
from ...framework.tensor import Tensor
from ...parallel import mesh as mesh_mod
from .group import Group, ReduceOp, _resolve, get_world_group


def _axis_of(group: Group) -> Optional[str]:
    return group.axis


def _in_spmd(axis: str) -> bool:
    return mesh_mod.inside_spmd_region(axis) if axis else False


def _reduce_fn(op):
    return {ReduceOp.SUM: jax.lax.psum, ReduceOp.MAX: jax.lax.pmax,
            ReduceOp.MIN: jax.lax.pmin,
            "sum": jax.lax.psum, "max": jax.lax.pmax, "min": jax.lax.pmin,
            }.get(op, jax.lax.psum)


def _sharded_axis(t, axis):
    """Which dim of the global array is sharded over `axis`, or None."""
    arr = unwrap(t)
    shd = getattr(arr, "sharding", None)
    if isinstance(shd, NamedSharding):
        for i, s in enumerate(shd.spec):
            names = s if isinstance(s, tuple) else (s,)
            if axis in [n for n in names if n]:
                return i
    return None


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    if axis and _in_spmd(axis):
        out = _reduce_fn(op)(unwrap(tensor), axis)
        if isinstance(tensor, Tensor):
            tensor._data = out
            return tensor
        return wrap(out)
    arr0 = unwrap(tensor)
    from jax.sharding import SingleDeviceSharding
    if jax.process_count() > 1 and isinstance(
            getattr(arr0, "sharding", None), SingleDeviceSharding):
        # true multi-controller: each process holds a process-LOCAL value
        # (single-device array); lift to a global [n_devices, ...] array
        # over the group axis (world maps to 'dp'), reduce under jit
        # (Gloo/ICI collective), read the replicated result back. This is
        # the ProcessGroup::AllReduce semantic of the reference
        # (process_group_nccl.cc:174). Global/replicated jax.Arrays fall
        # through to the GSPMD path below, where allreduce-of-synced
        # values is the identity.
        import numpy as _np
        ax = axis or "dp"
        mesh = mesh_mod.get_mesh()
        n = mesh.shape[ax]
        if n == 1:
            # size-1 group: reduce is the identity regardless of the
            # rest of the mesh
            return tensor
        if any(v > 1 for k, v in mesh.shape.items() if k != ax):
            # On a hybrid mesh the per-process addressable extent along
            # `ax` is not local_device_count, and — worse — a group
            # reduce over `ax` has a DIFFERENT result per coordinate of
            # the other axes, which this single-global-value path cannot
            # represent. Hybrid groups must reduce inside the jitted
            # SPMD region instead.
            raise NotImplementedError(
                f"multi-controller eager all_reduce needs group axis "
                f"{ax!r} to span the whole mesh (1-D world); on a hybrid "
                f"mesh {dict(mesh.shape)} run the collective inside the "
                f"jitted SPMD region (jax.lax.psum under shard_map/jit)")
        local_n = jax.local_device_count()
        a = _np.asarray(arr0)
        if op not in (ReduceOp.SUM, ReduceOp.AVG, ReduceOp.MAX,
                      ReduceOp.MIN):
            raise NotImplementedError(
                f"multi-process all_reduce op {op!r} is not supported")
        # each process contributes its value on local_n device rows
        # (dtype-preserving: no pre-scaling); SUM over-counts by local_n
        # and is corrected after the reduce — exactly divisible, so
        # integer tensors keep their dtype. AVG/MAX/MIN need no
        # correction (each process is equally over-represented).
        tile = _np.broadcast_to(a[None], (local_n,) + a.shape)
        gs = NamedSharding(mesh, PartitionSpec(ax))
        garr = jax.make_array_from_process_local_data(
            gs, _np.ascontiguousarray(tile), (n,) + tuple(a.shape))
        word = {ReduceOp.SUM: jnp.sum, ReduceOp.MAX: jnp.max,
                ReduceOp.MIN: jnp.min, ReduceOp.AVG: jnp.mean}[op]
        out = jax.jit(lambda g: word(g, axis=0),
                      out_shardings=NamedSharding(
                          mesh, PartitionSpec()))(garr)
        if op == ReduceOp.SUM and local_n > 1:
            if jnp.issubdtype(out.dtype, jnp.integer):
                out = out // local_n
            else:
                out = out / local_n
        local = jnp.asarray(out.addressable_data(0))
        if isinstance(tensor, Tensor):
            tensor._data = local
            return tensor
        return wrap(local)
    # eager/global view
    dim = _sharded_axis(tensor, axis) if axis else None
    if dim is None:
        # replicated along the group ⇒ values already equal; SUM of shared
        # value across a synced group is the value itself in global view
        return tensor
    arr = unwrap(tensor)
    mesh = mesh_mod.get_mesh()
    spec = [None] * arr.ndim
    spec[dim] = axis
    in_spec = PartitionSpec(*spec)

    def body(a):
        return _reduce_fn(op)(a, axis)

    fn = jax.jit(jax.shard_map(
        body, mesh=mesh, in_specs=(in_spec,),
        out_specs=PartitionSpec(*([None] * arr.ndim))))
    out = fn(arr)
    tensor._data = out
    return tensor


def all_gather(tensor_list, tensor, group=None, sync_op=True, axis=0):
    group = _resolve(group)
    gaxis = _axis_of(group)
    if gaxis and _in_spmd(gaxis):
        out = jax.lax.all_gather(unwrap(tensor), gaxis)
        parts = [wrap(out[i]) for i in range(out.shape[0])]
        if tensor_list is not None:
            tensor_list.extend(parts)
        return parts
    # global view: tensor is either sharded over gaxis (gather its shards) or
    # replicated (every "rank" holds the same value)
    n = group.nranks
    parts = [Tensor(unwrap(tensor)) for _ in range(n)] if \
        _sharded_axis(tensor, gaxis) is None else _split_shards(tensor, gaxis)
    if tensor_list is not None:
        tensor_list.extend(parts)
    return parts


def _split_shards(tensor, axis):
    arr = unwrap(tensor)
    dim = _sharded_axis(tensor, axis)
    n = mesh_mod.mesh_axis_size(axis)
    size = arr.shape[dim] // n
    return [Tensor(jax.lax.slice_in_dim(arr, i * size, (i + 1) * size, axis=dim))
            for i in range(n)]


def all_gather_object(object_list, obj, group=None):
    group = _resolve(group)
    object_list.extend([obj] * group.nranks)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    src = tensor_list if tensor_list is not None else tensor
    if axis and _in_spmd(axis):
        if isinstance(src, (list, tuple)):
            stacked = jnp.stack([unwrap(t) for t in src])
        else:
            stacked = unwrap(src)
        out = jax.lax.psum_scatter(stacked, axis, scatter_dimension=0,
                                   tiled=False)
        tensor._data = out
        return tensor
    return tensor


def broadcast(tensor, src=0, group=None, sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    if axis and _in_spmd(axis):
        arr = unwrap(tensor)
        src_rank = group.get_group_rank(src) if src in group.ranks else src
        idx = jax.lax.axis_index(axis)
        # select src's value: gather then index (XLA folds this)
        gathered = jax.lax.all_gather(arr, axis)
        tensor._data = gathered[src_rank]
        return tensor
    # global view: replicated arrays are already equal on all ranks
    return tensor


def reduce(tensor, dst=0, op=ReduceOp.SUM, group=None, sync_op=True):
    return all_reduce(tensor, op, group, sync_op)


def scatter(tensor, tensor_list=None, src=0, group=None, sync_op=True):
    group = _resolve(group)
    if tensor_list:
        idx = group.rank() if group.rank() >= 0 else 0
        tensor._data = unwrap(tensor_list[idx])
    return tensor


def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    if axis and _in_spmd(axis):
        stacked = jnp.stack([unwrap(t) for t in in_tensor_list])
        out = jax.lax.all_to_all(stacked, axis, split_axis=0, concat_axis=0,
                                 tiled=False)
        parts = [wrap(out[i]) for i in range(out.shape[0])]
        if out_tensor_list is not None:
            out_tensor_list.extend(parts)
        return parts
    if out_tensor_list is not None:
        out_tensor_list.extend(in_tensor_list)
    return in_tensor_list


all_to_all = alltoall


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    if axis and _in_spmd(axis):
        out = jax.lax.all_to_all(unwrap(in_tensor), axis, split_axis=0,
                                 concat_axis=0, tiled=True)
        out_tensor._data = out
        return out_tensor
    out_tensor._data = unwrap(in_tensor)
    return out_tensor


def send(tensor, dst=0, group=None, sync_op=True):
    group = _resolve(group)
    axis = _axis_of(group)
    if axis and _in_spmd(axis):
        # point-to-point on TPU = ppermute ring shift
        n = group.nranks
        perm = [(i, dst if n == 0 else (i + 1) % n) for i in range(n)]
        return wrap(jax.lax.ppermute(unwrap(tensor), axis, perm))
    return tensor


def recv(tensor, src=0, group=None, sync_op=True):
    return tensor


def isend(tensor, dst=0, group=None):
    return send(tensor, dst, group)


def irecv(tensor, src=0, group=None):
    return recv(tensor, src, group)


class P2POp:
    def __init__(self, op, tensor, peer, group=None):
        self.op = op
        self.tensor = tensor
        self.peer = peer
        self.group = group


def batch_isend_irecv(p2p_op_list):
    results = []
    for p in p2p_op_list:
        results.append(p.op(p.tensor, p.peer, p.group))
    return results


def barrier(group=None):
    jax.effects_barrier()
    for d in jax.devices():
        pass
    return None


def wait(tensor, group=None, use_calc_stream=True):
    u = unwrap(tensor)
    if hasattr(u, "block_until_ready"):
        u.block_until_ready()
    return tensor
