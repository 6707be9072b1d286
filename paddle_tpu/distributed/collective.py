"""Communication API over mesh axes.

Reference analog: python/paddle/distributed/communication/* (all_reduce,
all_gather, …, group.py:22 `Group`, collective.py:180 `new_group`) backed by
ProcessGroupNCCL (paddle/fluid/distributed/collective/process_group_nccl.cc).

TPU-native redesign: a Group names a mesh axis (or axis subset); an eager
collective on a sharded jax.Array is a *compiled* shard_map program over
that axis — XLA schedules it on ICI. On replicated/single-device values the
collectives are arithmetic no-ops matching a world of size 1 (the reference
behaves identically when world_size == 1, communication/all_reduce.py).

Inside traced code (to_static / the parallel engine / shard_map blocks) use
`paddle_tpu.distributed.functional` primitives (psum/all_gather/ppermute
wrappers) directly.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
# NamedSharding is imported for the isinstance probe in _axis_sharded
# only — construction goes through the paddle_tpu.sharding factories
# (the ONE placement authority, tracelint TL011)
from jax.sharding import Mesh, NamedSharding
from jax import shard_map

from ..core.tensor import Tensor
from ..sharding import named_sharding as _named_sharding, spec as _spec
from . import topology as topo_mod


class ReduceOp:
    SUM = 0
    MAX = 1
    MIN = 2
    PROD = 3
    AVG = 4


class Group:
    """A communication group = one mesh axis (reference: Group objects own an
    NCCL communicator, communication/group.py:22; here the 'communicator' is
    the compiled collective on the axis)."""

    def __init__(self, mesh: Mesh, axis: str):
        self.mesh = mesh
        self.axis = axis
        self.nranks = mesh.shape[axis]
        self.rank = 0  # single-controller: per-device rank exists in-program
        self.name = f"mesh_axis_{axis}"

    @property
    def world_size(self):
        return self.nranks

    def get_group_rank(self, rank):
        return rank

    @property
    def process_group(self):
        return self

    def __repr__(self):
        return f"Group(axis={self.axis!r}, nranks={self.nranks})"


def new_group(ranks=None, backend=None, timeout=None, axis=None):
    """Reference: collective.new_group (collective.py:180). On the mesh
    world, a new group must correspond to a mesh axis; arbitrary rank subsets
    are not addressable by compiled collectives — callers inside the fleet
    stack always use per-axis groups."""
    mesh = topo_mod.get_mesh()
    if mesh is None:
        hcg = _ensure_default_hcg()
        mesh = hcg.mesh
    if axis is None:
        # the common fleet internal call creates the world group
        axis = "dp"
    return Group(mesh, axis)


def _ensure_default_hcg():
    hcg = topo_mod.get_hybrid_communicate_group()
    if hcg is None:
        hcg = topo_mod.HybridCommunicateGroup(mesh=topo_mod.build_mesh(dp=-1))
        topo_mod.set_hybrid_communicate_group(hcg)
    return hcg


_REDUCERS = {
    ReduceOp.SUM: jax.lax.psum,
    ReduceOp.MAX: jax.lax.pmax,
    ReduceOp.MIN: jax.lax.pmin,
    ReduceOp.AVG: jax.lax.pmean,
    # no lax pprod: product = gather-then-reduce along the axis
    ReduceOp.PROD: lambda x, axis: jnp.prod(
        jax.lax.all_gather(x, axis), axis=0),
}


def _strip_axis(entry, axis):
    """Remove `axis` from one PartitionSpec entry (handles fused tuples like
    ('dp','sharding'))."""
    if entry == axis:
        return None
    if isinstance(entry, tuple):
        kept = tuple(a for a in entry if a != axis)
        return kept if len(kept) > 1 else (kept[0] if kept else None)
    return entry


def _axis_sharded(value, mesh, axis):
    """True if `value` is actually partitioned along `axis` of `mesh`."""
    sh = getattr(value, "sharding", None)
    if not isinstance(sh, NamedSharding) or sh.mesh.shape != mesh.shape:
        return False
    for entry in sh.spec:
        if entry == axis or (isinstance(entry, tuple) and axis in entry):
            return True
    return False


def _collective_over_axis(value, mesh, axis, per_shard_fn, out_spec_fn):
    """Run per_shard_fn over the shards of `value` along `axis` via a
    compiled shard_map program; other mesh axes are untouched."""
    sh = value.sharding
    in_spec = sh.spec
    out_spec = out_spec_fn(in_spec)
    fn = shard_map(per_shard_fn, mesh=mesh, in_specs=(in_spec,),
                   out_specs=out_spec, check_vma=False)
    return jax.jit(fn)(value)


def _unwrap(t):
    return t._value if isinstance(t, Tensor) else jnp.asarray(t)


def _spawned_store(group):
    """(rank, world, store) when the env contract declares MORE processes
    than the local jax world (dist.spawn / launch children without
    jax.distributed) and the caller didn't name a local mesh group.

    In that regime the local mesh has no cross-process identity, so the
    mesh path would silently reduce over a world of one — the silent-no-op
    bug flagged by the round-2 advisor (env.py get_world_size reports the
    env contract). Dense collectives must ride the coordination store (like
    p2p.reduce) or fail loudly."""
    if group is not None:
        return None
    from .env import get_rank, get_world_size, get_store
    world = get_world_size()
    if world <= jax.process_count():
        return None
    store = get_store()
    if store is None:
        raise RuntimeError(
            f"distributed env declares world_size={world} but this process "
            f"has no coordination store and no multi-process jax runtime — "
            "a mesh collective here would silently act on this process "
            "alone. Initialize the store (dist.init_parallel_env / spawn "
            "context) before calling dense collectives.")
    return get_rank(), world, store


def _store_all_gather_arrays(x_np):
    from .p2p import all_gather_object
    objs = []
    all_gather_object(objs, np.asarray(x_np))
    return [np.asarray(o) for o in objs]


_NP_FOLD = {
    ReduceOp.SUM: lambda arrs: np.sum(arrs, axis=0),
    ReduceOp.MAX: lambda arrs: np.max(arrs, axis=0),
    ReduceOp.MIN: lambda arrs: np.min(arrs, axis=0),
    ReduceOp.PROD: lambda arrs: np.prod(arrs, axis=0),
    ReduceOp.AVG: lambda arrs: np.mean(arrs, axis=0),
}


def all_reduce(tensor, op=ReduceOp.SUM, group=None, sync_op=True):
    """In-place all-reduce (reference: communication/all_reduce.py). On a
    value sharded over the group axis: psum across shards (result replicated
    on that axis). On a replicated value: identity (world of one). On a
    spawned multi-process job (env world > local jax world): folds through
    the coordination store so gradients really sync across processes."""
    sp = _spawned_store(group)
    if sp is not None:
        arrs = _store_all_gather_arrays(_unwrap(tensor))
        out = jnp.asarray(_NP_FOLD[op](np.stack(arrs)))
        if isinstance(tensor, Tensor):
            tensor._value = out
            return tensor
        return Tensor(out)
    if group is None:
        group = new_group(axis="dp")
    v = _unwrap(tensor)
    if group.nranks == 1 or not _axis_sharded(v, group.mesh, group.axis):
        return tensor
    if op not in _REDUCERS:
        raise ValueError(f"unsupported ReduceOp {op}")
    lax_red = _REDUCERS[op]
    axis = group.axis

    def body(x):
        return lax_red(x, axis)

    def out_spec(spec):
        return _spec(*[_strip_axis(e, axis) for e in spec])

    out = _collective_over_axis(v, group.mesh, axis, body, out_spec)
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def all_gather(tensor_list, tensor, group=None, sync_op=True):
    """Reference: communication/all_gather.py — gathers shards along the
    group axis into tensor_list (one entry per shard)."""
    sp = _spawned_store(group)
    if sp is not None:
        arrs = _store_all_gather_arrays(_unwrap(tensor))
        tensor_list.clear()
        tensor_list.extend(Tensor(jnp.asarray(a)) for a in arrs)
        return
    if group is None:
        group = new_group(axis="dp")
    v = _unwrap(tensor)
    if group.nranks == 1 or not _axis_sharded(v, group.mesh, group.axis):
        tensor_list.clear()
        tensor_list.extend([Tensor(v) for _ in range(group.nranks)])
        return
    axis = group.axis

    def body(x):
        return jax.lax.all_gather(x, axis)

    def out_spec(spec):
        return _spec(*([None] + [_strip_axis(e, axis) for e in spec]))

    out = _collective_over_axis(v, group.mesh, axis, body, out_spec)
    tensor_list.clear()
    for i in range(group.nranks):
        tensor_list.append(Tensor(out[i]))


def broadcast(tensor, src=0, group=None, sync_op=True):
    """Reference: communication/broadcast.py. Mesh semantics: make the value
    replicated along the group axis, taking shard `src`."""
    sp = _spawned_store(group)
    if sp is not None:
        from .p2p import broadcast_object_list
        box = [np.asarray(_unwrap(tensor))]
        broadcast_object_list(box, src=src)
        v = jnp.asarray(box[0])
        if isinstance(tensor, Tensor):
            tensor._value = v
            return tensor
        return Tensor(v)
    if group is None:
        group = new_group(axis="dp")
    v = _unwrap(tensor)
    if group.nranks == 1 or not _axis_sharded(v, group.mesh, group.axis):
        return tensor
    axis = group.axis

    def body(x):
        gathered = jax.lax.all_gather(x, axis)
        return gathered[src]

    def out_spec(spec):
        return _spec(*[_strip_axis(e, axis) for e in spec])

    out = _collective_over_axis(v, group.mesh, axis, body, out_spec)
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def reduce_scatter(tensor, tensor_list=None, op=ReduceOp.SUM, group=None,
                   sync_op=True):
    """Reference: communication/reduce_scatter.py. Controller semantics:
    out's shard r = sum over ranks k of rank k's r-th chunk. With inputs
    replicated over the axis (every rank holds the same data) that is
    nranks * chunk_r, computed with no collective at all; with inputs
    sharded over the axis (true per-rank values) it is a psum_scatter."""
    sp = _spawned_store(group)
    if sp is not None:
        rank, world, _ = sp
        src_t = tensor_list if tensor_list is not None else tensor
        if isinstance(src_t, (list, tuple)):
            mine = np.stack([np.asarray(_unwrap(t)) for t in src_t])
        else:
            mine = np.asarray(_unwrap(src_t))
        arrs = _store_all_gather_arrays(mine)
        total = _NP_FOLD[op](np.stack(arrs))
        chunk = total.shape[0] // world
        out = jnp.asarray(total[rank * chunk:(rank + 1) * chunk])
        if isinstance(src_t, (list, tuple)) and chunk == 1:
            out = out[0]
        if isinstance(tensor, Tensor):
            tensor._value = out
            return tensor
        return Tensor(out)
    if group is None:
        group = new_group(axis="dp")
    src = tensor_list if tensor_list is not None else tensor
    if isinstance(src, (list, tuple)):
        v = jnp.stack([_unwrap(t) for t in src])
        axis0_stacked = True
    else:
        v = _unwrap(src)
        axis0_stacked = False
    if group.nranks == 1:
        out = v[0] if axis0_stacked else v
        if isinstance(tensor, Tensor):
            tensor._value = out
            return tensor
        return Tensor(out)
    mesh, axis = group.mesh, group.axis
    n = group.nranks
    if v.shape[0] % n != 0:
        raise ValueError(
            f"reduce_scatter dim0 {v.shape[0]} not divisible by {n}")
    if not _axis_sharded(v, mesh, axis):
        # replicated input: out shard r = n * chunk_r — just scale and shard
        spec = [axis] + [None] * (v.ndim - 1)
        out = jax.device_put(v * n, _named_sharding(mesh, spec))
    else:
        if (v.shape[0] // n) % n != 0:
            raise ValueError(
                f"per-rank chunk dim0 {v.shape[0] // n} not divisible by "
                f"{n} ranks")

        def body(x):
            return jax.lax.psum_scatter(x, axis, tiled=True)

        def out_spec(spec):
            return _spec(*[axis if i == 0 else e
                           for i, e in enumerate(spec)])

        out = _collective_over_axis(v, mesh, axis, body, out_spec)
    if isinstance(tensor, Tensor):
        tensor._value = out
        return tensor
    return Tensor(out)


def all_to_all(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """Reference: communication/all_to_all.py. Controller semantics: each
    in_tensor_list[i] is sharded over the group axis (shard r = rank r's
    i-th tensor); out[j]'s shard r = in[r]'s shard j."""
    sp = _spawned_store(group)
    if sp is not None:
        rank, world, _ = sp
        if len(in_tensor_list) != world:
            raise ValueError(
                f"all_to_all needs one tensor per rank ({world}), got "
                f"{len(in_tensor_list)}")
        mine = np.stack([np.asarray(_unwrap(t)) for t in in_tensor_list])
        arrs = _store_all_gather_arrays(mine)
        out_tensor_list.clear()
        out_tensor_list.extend(
            Tensor(jnp.asarray(arrs[r][rank])) for r in range(world))
        return
    if group is None:
        group = new_group(axis="dp")
    vals = [_unwrap(t) for t in in_tensor_list]
    if group.nranks == 1:
        out_tensor_list.clear()
        out_tensor_list.extend([Tensor(v) for v in vals])
        return
    if len(vals) != group.nranks:
        raise ValueError(
            f"all_to_all needs one tensor per rank ({group.nranks}), "
            f"got {len(vals)}")
    mesh, axis = group.mesh, group.axis
    if not all(_axis_sharded(v, mesh, axis) for v in vals):
        raise ValueError(
            "eager all_to_all requires inputs sharded over the group axis "
            "(per-rank values live in the shards); replicated inputs have "
            "no per-rank identity on a single controller")
    stacked = jnp.stack(vals)  # [nranks, global0, ...]
    in_spec = _spec(*([None] + list(vals[0].sharding.spec)))

    def body(x):
        # x: [nranks, shard...]; exchange dim0 across the axis ring
        return jax.lax.all_to_all(x, axis, split_axis=0, concat_axis=0,
                                  tiled=True)

    fn = shard_map(body, mesh=mesh, in_specs=(in_spec,), out_specs=in_spec,
                   check_vma=False)
    out = jax.jit(fn)(jax.device_put(stacked,
                                     _named_sharding(mesh, in_spec)))
    out_tensor_list.clear()
    for i in range(group.nranks):
        out_tensor_list.append(Tensor(out[i]))


def barrier(group=None):
    """Reference: communication/barrier.py.

    Multi-process job: a REAL cross-process barrier over the native
    coordination store (native/coord_store.cc) — `block_until_ready` says
    nothing about other processes. Single controller: wait for the
    locally-issued device work (`device.synchronize`)."""
    from .env import get_store, get_world_size, get_rank
    store = get_store()
    if store is not None and get_world_size() > 1:
        store.barrier(name="dist_barrier", world_size=get_world_size())
        return
    from ..device import synchronize
    synchronize()


def get_group(axis="dp"):
    return new_group(axis=axis)


# Eager point-to-point + gather/reduce live in p2p.py (host-mediated; the
# compiled path is lax.ppermute inside shard_map / pipeline schedules).
from .p2p import (  # noqa: E402,F401
    send, recv, isend, irecv, P2POp, P2PTask, batch_isend_irecv, gather,
    scatter, reduce, all_gather_object, broadcast_object_list,
)
