"""DTensor surface: ProcessMesh + placements + shard/reshard.

See package docstring for the reference mapping. Everything here is thin by
design: the heavy machinery the reference implements by hand (SPMD rules,
reshard transforms, dist branches in every generated API) is delegated to
GSPMD/XLA. Cited parity points:
  - ProcessMesh           ≈ auto_parallel/process_mesh.py:71
  - Shard/Replicate/Partial ≈ auto_parallel/placement_type.py
  - shard_tensor          ≈ auto_parallel/api.py:118
  - dtensor_from_fn       ≈ auto_parallel/api.py:248
  - reshard               ≈ auto_parallel/api.py:282
  - shard_layer           ≈ auto_parallel/api.py:381
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import Mesh, NamedSharding  # isinstance checks only

from ... import sharding as _shardlib
from ...core.tensor import Tensor
from ...nn.layer.layers import Layer

__all__ = [
    "ProcessMesh", "Shard", "Replicate", "Partial", "shard_tensor",
    "dtensor_from_fn", "reshard", "shard_layer", "get_placements",
    "placements_to_spec",
]


# --------------------------------------------------------------------------
# Placements (reference: placement_type.py)
# --------------------------------------------------------------------------

class Placement:
    def is_shard(self, dim=None):
        return False

    def is_replicated(self):
        return False

    def is_partial(self):
        return False


class Shard(Placement):
    """Tensor dim `dim` is split over the corresponding mesh dimension."""

    def __init__(self, dim):
        self.dim = int(dim)

    def get_dim(self):
        return self.dim

    def is_shard(self, dim=None):
        return dim is None or dim == self.dim

    def __eq__(self, other):
        return isinstance(other, Shard) and other.dim == self.dim

    def __hash__(self):
        return hash(("Shard", self.dim))

    def __repr__(self):
        return f"Shard(dim={self.dim})"


class Replicate(Placement):
    def is_replicated(self):
        return True

    def __eq__(self, other):
        return isinstance(other, Replicate)

    def __hash__(self):
        return hash("Replicate")

    def __repr__(self):
        return "Replicate()"


class Partial(Placement):
    """Pending reduction over the mesh dim (reference: partial status with
    a reduce_type). Eagerly materialized as replicated-with-debt; the psum
    happens on reshard to Replicate/Shard."""

    def __init__(self, reduce_type="sum"):
        self.reduce_type = reduce_type

    def is_partial(self):
        return True

    def __eq__(self, other):
        return isinstance(other, Partial) and \
            other.reduce_type == self.reduce_type

    def __hash__(self):
        return hash(("Partial", self.reduce_type))

    def __repr__(self):
        return f"Partial(reduce_type={self.reduce_type!r})"


# --------------------------------------------------------------------------
# ProcessMesh (reference: process_mesh.py:71)
# --------------------------------------------------------------------------

class ProcessMesh:
    """N-D grid of device/process ids with named dims. Owns the equivalent
    jax.sharding.Mesh; placements index its dims."""

    def __init__(self, mesh, dim_names=None, *, devices=None):
        arr = np.asarray(mesh)
        if dim_names is None:
            dim_names = [f"d{i}" for i in range(arr.ndim)]
        if len(dim_names) != arr.ndim:
            raise ValueError(
                f"dim_names {dim_names} do not match mesh ndim {arr.ndim}")
        self._ids = arr
        self._dim_names = list(dim_names)
        devices = devices if devices is not None else jax.devices()
        ids = arr.reshape(-1).tolist()
        if len(set(ids)) != len(ids):
            raise ValueError(f"mesh has duplicate process ids: {sorted(ids)}")
        if ids and (min(ids) < 0 or max(ids) >= len(devices)):
            raise ValueError(
                f"mesh process ids span [{min(ids)}, {max(ids)}], but only "
                f"{len(devices)} devices are available")
        dev_arr = np.empty(arr.shape, dtype=object)
        for idx in np.ndindex(arr.shape):
            dev_arr[idx] = devices[int(arr[idx])]
        self._jax_mesh = Mesh(dev_arr, tuple(self._dim_names))

    @property
    def mesh(self):
        return self._ids

    @property
    def dim_names(self):
        return list(self._dim_names)

    @property
    def shape(self):
        return list(self._ids.shape)

    @property
    def ndim(self):
        return self._ids.ndim

    @property
    def process_ids(self):
        return [int(x) for x in self._ids.flatten()]

    @property
    def jax_mesh(self) -> Mesh:
        return self._jax_mesh

    def get_dim_size(self, name):
        return self._ids.shape[self._dim_names.index(name)]

    def __eq__(self, other):
        return (isinstance(other, ProcessMesh)
                and np.array_equal(self._ids, other._ids)
                and self._dim_names == other._dim_names)

    def __hash__(self):
        return hash((self._ids.tobytes(), tuple(self._dim_names)))

    def __repr__(self):
        return (f"ProcessMesh(shape={self.shape}, "
                f"dim_names={self._dim_names})")


def _as_jax_mesh(mesh):
    if isinstance(mesh, ProcessMesh):
        return mesh.jax_mesh
    if isinstance(mesh, Mesh):
        return mesh
    raise TypeError(f"expected ProcessMesh or jax Mesh, got {type(mesh)}")


# --------------------------------------------------------------------------
# placements <-> PartitionSpec
# --------------------------------------------------------------------------

def placements_to_spec(mesh, placements, ndim):
    """[per-mesh-dim placement] → PartitionSpec over tensor dims. A tensor
    dim sharded by several mesh dims gets a tuple entry (GSPMD multi-axis
    sharding), ordered by mesh dim."""
    jmesh = _as_jax_mesh(mesh)
    names = jmesh.axis_names
    if len(placements) != len(names):
        raise ValueError(
            f"need one placement per mesh dim ({len(names)}), "
            f"got {len(placements)}")
    entries = [[] for _ in range(ndim)]
    partials = {}
    for mesh_dim, pl in enumerate(placements):
        if isinstance(pl, Shard):
            d = pl.dim if pl.dim >= 0 else pl.dim + ndim
            if d < 0 or d >= ndim:
                raise ValueError(f"Shard dim {pl.dim} out of range for "
                                 f"ndim {ndim}")
            entries[d].append(names[mesh_dim])
        elif isinstance(pl, Partial):
            partials[names[mesh_dim]] = pl.reduce_type
        elif not isinstance(pl, (Replicate, type(None))):
            raise TypeError(f"unknown placement {pl!r}")
    spec = _shardlib.spec(*[
        None if not e else (e[0] if len(e) == 1 else tuple(e))
        for e in entries])
    return spec, partials


def _spec_to_placements(mesh, spec, ndim):
    jmesh = _as_jax_mesh(mesh)
    names = list(jmesh.axis_names)
    placements = [Replicate() for _ in names]
    entries = list(spec) + [None] * (ndim - len(list(spec)))
    for tdim, e in enumerate(entries):
        if e is None:
            continue
        for ax in ([e] if isinstance(e, str) else list(e)):
            placements[names.index(ax)] = Shard(tdim)
    return placements


def get_placements(tensor, mesh=None):
    """Placements of a (D)Tensor: from its jax sharding + any pending
    Partial annotation (reference: Tensor.placements)."""
    val = tensor._value if isinstance(tensor, Tensor) else tensor
    sharding = getattr(val, "sharding", None)
    mesh = mesh or getattr(sharding, "mesh", None)
    if mesh is None or not isinstance(sharding, NamedSharding):
        return None
    placements = _spec_to_placements(mesh, sharding.spec, val.ndim)
    partials = getattr(tensor, "_partial_axes", None) or {}
    names = list(_as_jax_mesh(mesh).axis_names)
    for ax, rt in partials.items():
        placements[names.index(ax)] = Partial(rt)
    return placements


# --------------------------------------------------------------------------
# shard_tensor / dtensor_from_fn / reshard / shard_layer
# --------------------------------------------------------------------------

def shard_tensor(data, mesh, placements, *, dtype=None, stop_gradient=None):
    """Create a distributed tensor from data + placements (reference:
    api.py:118). The result is an ordinary Tensor whose value carries a
    NamedSharding — every downstream op is GSPMD-partitioned."""
    t = data if isinstance(data, Tensor) else Tensor(np.asarray(data))
    jmesh = _as_jax_mesh(mesh)
    spec, partials = placements_to_spec(mesh, placements, t.ndim)
    if partials:
        raise ValueError(
            "shard_tensor cannot create a Partial tensor from data "
            "(the reference only produces partial tensors as op outputs); "
            "use Replicate() or Shard()")
    val = t._value
    if dtype is not None:
        from ...core.dtype import convert_dtype
        val = val.astype(convert_dtype(dtype))
    out = Tensor(jax.device_put(val, _shardlib.named_sharding(jmesh, spec)))
    out.stop_gradient = (t.stop_gradient if stop_gradient is None
                         else stop_gradient)
    out.process_mesh = mesh if isinstance(mesh, ProcessMesh) else None
    return out


def dtensor_from_fn(fn, mesh, placements, *args, **kwargs):
    """Build the tensor with `fn` then place it (reference: api.py:248).
    On TPU the interesting case — creating the value already-sharded so no
    host copy of the global tensor exists — is handled by jax.jit with
    out_shardings."""
    jmesh = _as_jax_mesh(mesh)

    def call():
        out = fn(*args, **kwargs)
        return out._value if isinstance(out, Tensor) else out

    probe = jax.eval_shape(call)
    spec, partials = placements_to_spec(mesh, placements, len(probe.shape))
    if partials:
        raise ValueError("dtensor_from_fn cannot produce Partial outputs")
    val = jax.jit(call,
                  out_shardings=_shardlib.named_sharding(jmesh, spec))()
    out = Tensor(val)
    out.process_mesh = mesh if isinstance(mesh, ProcessMesh) else None
    return out


def reshard(tensor, mesh, placements):
    """Change placements (reference: api.py:282 + the C++ reshard rule zoo
    r_to_s/s_to_r/p_to_r/…). All source→target pairs collapse to:
      1. pending Partial? psum over those axes (p_to_r / p_to_s),
      2. device_put to the target NamedSharding (XLA moves the bytes —
         slice for r_to_s, all-gather for s_to_r, collective-permute for
         s_to_s')."""
    if not isinstance(tensor, Tensor):
        tensor = Tensor(tensor)
    jmesh = _as_jax_mesh(mesh)
    spec, target_partials = placements_to_spec(mesh, placements, tensor.ndim)
    val = tensor._value
    pending = dict(getattr(tensor, "_partial_axes", None) or {})
    # resolve pending partials the target doesn't keep
    resolve = [ax for ax in pending if ax not in target_partials]
    if resolve:
        cur = val.sharding.spec if isinstance(val.sharding, NamedSharding) \
            else _shardlib.spec(*([None] * val.ndim))

        def body(v):
            for ax in resolve:
                v = jax.lax.psum(v, ax)
            return v

        val = shard_map(
            body, mesh=jmesh, in_specs=cur, out_specs=cur,
            check_vma=False)(val)
        for ax in resolve:
            pending.pop(ax)
    val = jax.device_put(val, _shardlib.named_sharding(jmesh, spec))
    new_partials = [ax for ax in target_partials if ax not in pending]
    if new_partials:
        # r_to_p: the value survives only on coordinate 0 of each new
        # partial axis, other shards hold zeros — so p_to_r's psum later
        # reproduces the original value (reference r_to_p_reshard_function)
        def zero_rest(v):
            for ax in new_partials:
                idx = jax.lax.axis_index(ax)
                v = jnp.where(idx == 0, v, jnp.zeros_like(v))
            return v

        val = shard_map(zero_rest, mesh=jmesh, in_specs=spec,
                        out_specs=spec, check_vma=False)(val)
    out = Tensor(val)
    out.stop_gradient = tensor.stop_gradient
    if pending or target_partials:
        out._partial_axes = {**pending, **target_partials}
    out.process_mesh = mesh if isinstance(mesh, ProcessMesh) else None
    return out


def shard_layer(layer: Layer, mesh, shard_fn=None, input_fn=None,
                output_fn=None):
    """Shard a layer's parameters in-place (reference: api.py:381).

    shard_fn(sublayer_name, sublayer, mesh) places each sublayer's params
    (via shard_tensor); default replicates everything on the mesh. input_fn/
    output_fn wrap forward to place activations."""
    jmesh = _as_jax_mesh(mesh)

    def default_shard_fn(name, sub, mesh):
        for pname, p in list(sub._parameters.items()):
            n = len(jmesh.axis_names)
            placed = shard_tensor(p, mesh, [Replicate()] * n)
            p._value = placed._value

    fn = shard_fn or default_shard_fn
    for name, sub in layer.named_sublayers(include_self=True):
        fn(name, sub, mesh)
    if input_fn is not None:
        layer.register_forward_pre_hook(
            lambda lyr, inputs: input_fn(inputs, mesh))
    if output_fn is not None:
        layer.register_forward_post_hook(
            lambda lyr, inputs, outputs: output_fn(outputs, mesh))
    return layer
