"""group_sharded_parallel facade (ZeRO levels by name).

Reference: python/paddle/distributed/sharding/group_sharded.py:44
`group_sharded_parallel(model, optimizer, level, ...)` which wraps the
model in GroupShardedStage2/3 and the optimizer in the sharded
optimizer, and `save_group_sharded_model`.

TPU-native: the ZeRO stages are *shardings*, not wrapper modules. The
facade places every parameter (and, through the train-step engine, every
optimizer slot) with the stage-appropriate NamedSharding over the
'sharding' mesh axis; XLA/GSPMD then derives the gather/reduce-scatter
traffic the reference's stage2/stage3 wrappers issue by hand. The model
and optimizer objects come back unwrapped — eager ops and the jitted
engine both see sharded arrays.
"""
from __future__ import annotations

import jax

from . import topology as topo_mod
from ..sharding import named_sharding as _named_sharding
from .sharding_spec import DEFAULT_TP_RULES, spec_for_param

_LEVELS = {"os": 1, "os_g": 2, "p_g_os": 3}


def group_sharded_parallel(model, optimizer, level, scaler=None, group=None,
                           offload=False, sync_buffers=False,
                           buffer_max_size=2 ** 23, segment_size=2 ** 20,
                           sync_comm=False, dp_group=None,
                           exclude_layer=None):
    """Returns (model, optimizer, scaler) with stage-`level` sharding
    applied. `level`: 'os' (ZeRO-1), 'os_g' (ZeRO-2), 'p_g_os' (ZeRO-3).

    `offload=True` parks parameters in host memory (jax memories API) —
    the analog of the reference's cpu_offload flag."""
    if level not in _LEVELS:
        raise ValueError(
            f"level must be one of {sorted(_LEVELS)}, got {level!r}")
    stage = _LEVELS[level]
    hcg = topo_mod.get_hybrid_communicate_group()
    if hcg is None:
        hcg = topo_mod.HybridCommunicateGroup(
            mesh=topo_mod.build_mesh(sharding=-1))
        topo_mod.set_hybrid_communicate_group(hcg)
    mesh = hcg.mesh

    for name, p in model.named_parameters():
        spec = spec_for_param(name, p, DEFAULT_TP_RULES,
                              sharding_stage=stage, mesh=mesh)
        sh = _named_sharding(mesh, spec)
        if offload:
            sh = sh.with_memory_kind("pinned_host")
        p._value = jax.device_put(p._value, sh)
        p.dist_spec = tuple(spec)

    # The train-step engine reads this to shard grads (stage>=2) and
    # optimizer slots (stage>=1) the same way.
    optimizer._group_sharded_stage = stage
    model._group_sharded_stage = stage
    if scaler is not None:
        scaler._group_sharded = True
    return model, optimizer, scaler


def save_group_sharded_model(model, output, optimizer=None):
    """Reference: sharding/group_sharded.py save_group_sharded_model —
    persists the (logically global) parameters; on the controller the
    sharded arrays already reassemble transparently."""
    import os
    from .. import framework_io
    os.makedirs(output, exist_ok=True)
    framework_io.save(model.state_dict(),
                      os.path.join(output, "model.pdparams"))
    if optimizer is not None and hasattr(optimizer, "state_dict"):
        framework_io.save(optimizer.state_dict(),
                          os.path.join(output, "model.pdopt"))
