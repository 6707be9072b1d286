"""Distributed surface completions (reference: the tail of
python/paddle/distributed/__init__.py — alltoall_single, dist.split,
shard_optimizer, DistModel/Strategy/to_static, PS dataset configs,
backend introspection, gloo CPU barrier trio).
"""
from __future__ import annotations

import contextlib

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from . import collective as C
from .env import get_rank, get_world_size, get_store

__all__ = [
    "alltoall", "alltoall_single", "scatter_object_list", "wait",
    "get_backend", "is_available", "destroy_process_group",
    "gloo_init_parallel_env", "gloo_barrier", "gloo_release",
    "ReduceType", "DistAttr", "split", "shard_optimizer",
    "unshard_dtensor", "Strategy", "DistModel", "to_static",
    "InMemoryDataset", "QueueDataset", "CountFilterEntry",
    "ProbabilityEntry", "ShowClickEntry",
]


# -- comm tail ---------------------------------------------------------------

def alltoall(out_tensor_list, in_tensor_list, group=None, sync_op=True):
    """Alias surface of collective.all_to_all (reference keeps both)."""
    return C.all_to_all(out_tensor_list, in_tensor_list, group=group,
                        sync_op=sync_op)


def alltoall_single(out_tensor, in_tensor, in_split_sizes=None,
                    out_split_sizes=None, group=None, sync_op=True):
    """Reference: communication/all_to_all.py alltoall_single — exchange
    contiguous dim0 blocks of ONE tensor across ranks.

    Controller semantics match the other dense collectives: a value
    actually sharded over the group axis exchanges blocks via the compiled
    lax.all_to_all; a replicated value is the world-of-one arithmetic
    no-op (every rank holds identical data, so the exchange returns the
    same tensor)."""
    if group is None:
        group = C.new_group(axis="dp")
    v = in_tensor._value if isinstance(in_tensor, Tensor) \
        else jnp.asarray(in_tensor)
    if group.nranks > 1 and C._axis_sharded(v, group.mesh, group.axis):
        from jax import shard_map
        spec = v.sharding.spec

        def body(x):
            return jax.lax.all_to_all(x, group.axis, split_axis=0,
                                      concat_axis=0, tiled=True)

        fn = shard_map(body, mesh=group.mesh, in_specs=(spec,),
                       out_specs=spec, check_vma=False)
        res = jax.jit(fn)(v)
    else:
        res = v
    if isinstance(out_tensor, Tensor):
        out_tensor._value = res
        return out_tensor
    return Tensor(res)


def scatter_object_list(out_object_list, in_object_list=None, src=0,
                        group=None):
    """Reference: communication/scatter.py scatter_object_list."""
    import pickle
    world, rank = get_world_size(), get_rank()
    if world == 1:
        out_object_list[:] = [in_object_list[0]] if in_object_list else []
        return
    store = get_store()
    if store is None:
        raise RuntimeError("scatter_object_list needs a launched job store")
    from .p2p import _obj_seq
    seq = _obj_seq["scatter_obj"]
    _obj_seq["scatter_obj"] += 1
    if rank == src:
        for r in range(world):
            store.set(f"obj/scatter/{seq}/{r}",
                      pickle.dumps(in_object_list[r]))
    mine = pickle.loads(store.wait(f"obj/scatter/{seq}/{rank}"))
    out_object_list[:] = [mine]
    done = store.add(f"obj/scatter/{seq}/done", 1)
    if done == world:
        for r in range(world):
            store.delete_key(f"obj/scatter/{seq}/{r}")
        store.delete_key(f"obj/scatter/{seq}/done")


def wait(tensor, group=None, use_calc_stream=True):
    """Reference: communication/wait.py — fence a collective's result."""
    jax.block_until_ready(tensor._value if isinstance(tensor, Tensor)
                          else tensor)
    return tensor


def get_backend(group=None):
    """Reference: get_backend returns 'NCCL'/'GLOO'; the comm backend here
    is XLA's compiled collectives over ICI/DCN."""
    return "XLA"


def is_available():
    """Reference: distributed.is_available."""
    return True


def destroy_process_group(group=None):
    """Reference: destroy_process_group — tear down comm state. Drops the
    process-global HCG (compiled collectives hold no persistent comms)."""
    from . import topology as topo_mod
    topo_mod.set_hybrid_communicate_group(None)


def gloo_init_parallel_env(rank_id, rank_num, server_endpoint):
    """Reference: CPU-only gloo bootstrap trio. The native coordination
    store plays gloo's role here."""
    import os
    os.environ.setdefault("PADDLE_TPU_PROCESS_ID", str(rank_id))
    os.environ.setdefault("PADDLE_TPU_NUM_PROCESSES", str(rank_num))
    os.environ.setdefault("PADDLE_TPU_COORDINATOR", server_endpoint)
    from .env import init_parallel_env
    init_parallel_env()


def gloo_barrier():
    C.barrier()


def gloo_release():
    destroy_process_group()


class ReduceType:
    """Reference: auto_parallel ReduceType for Partial placements."""
    kRedSum = 0
    kRedMax = 1
    kRedMin = 2
    kRedProd = 3
    kRedAvg = 4


class DistAttr:
    """Reference: DistAttr(mesh, placements) — static-graph dist attr."""

    def __init__(self, mesh, placements):
        self.process_mesh = mesh
        self.placements = placements

    def __repr__(self):
        return f"DistAttr(mesh={self.process_mesh}, " \
               f"placements={self.placements})"


# -- TP split / dtensor tail -------------------------------------------------

def split(x, size, operation, axis=0, num_partitions=1, gather_out=True,
          weight_attr=None, bias_attr=None, name=None):
    """Reference: distributed/parallel.py split — build a model-parallel
    linear/embedding whose weight is partitioned across the mp axis.
    Mesh-native: the weight is shard_tensor'd over 'mp'; GSPMD inserts the
    partial-sum all-reduce (linear) or gather (embedding)."""
    import paddle_tpu as paddle
    from . import topology as topo_mod

    hcg = topo_mod.get_hybrid_communicate_group()
    mesh = hcg.mesh if hcg is not None else None
    if operation == "linear":
        in_f, out_f = size
        w = paddle.randn([in_f, out_f]) * (1.0 / np.sqrt(in_f))
        if mesh is not None and mesh.shape.get("mp", 1) > 1:
            from ..sharding import named_sharding, spec as spec_of
            sp = spec_of(None, "mp") if axis == 1 else spec_of("mp", None)
            w._value = jax.device_put(w._value, named_sharding(mesh, sp))
        return paddle.matmul(x, w)
    if operation == "embedding":
        vocab, dim = size
        w = paddle.randn([vocab, dim]) * 0.02
        if mesh is not None and mesh.shape.get("mp", 1) > 1:
            from ..sharding import named_sharding, spec as spec_of
            w._value = jax.device_put(
                w._value, named_sharding(mesh, spec_of("mp", None)))
        from ..nn.functional import embedding
        return embedding(x, w)
    raise ValueError(f"split: unknown operation {operation!r}")


def shard_optimizer(optimizer, shard_fn=None):
    """Reference: auto_parallel/api.py shard_optimizer — optimizer states
    follow their parameters' shardings. States here are created by the
    engine with the param's sharding already; this wraps step() to apply
    shard_fn to newly created state tensors."""
    if shard_fn is None:
        return optimizer
    orig_step = optimizer.step

    def step(*a, **k):
        out = orig_step(*a, **k)
        for attr, val in vars(optimizer).items():
            if isinstance(val, dict):
                for key, st in val.items():
                    if isinstance(st, Tensor):
                        val[key] = shard_fn(key, None, st)
        return out

    optimizer.step = step
    return optimizer


def unshard_dtensor(dist_tensor):
    """Reference: auto_parallel/api.py unshard_dtensor — gather a
    sharded tensor to a fully replicated one."""
    v = dist_tensor._value if isinstance(dist_tensor, Tensor) \
        else dist_tensor
    sh = getattr(v, "sharding", None)
    if sh is not None and hasattr(sh, "mesh"):
        from ..sharding import replicated
        v = jax.device_put(v, replicated(sh.mesh))
    return Tensor(v)


# -- auto-parallel static API (DistModel / Strategy / to_static) ------------

class Strategy:
    """Reference: auto_parallel/strategy.py Strategy — config bundle the
    static Engine consumes (sharding/amp/recompute/pipeline sub-configs)."""

    class _Cfg:
        def __init__(self, **kw):
            self.__dict__.update(kw)

    def __init__(self, config=None):
        cfg = config or {}
        self.sharding = Strategy._Cfg(enable=False, degree=1, stage=1,
                                      **cfg.get("sharding", {}))
        self.amp = Strategy._Cfg(enable=False, dtype="bfloat16",
                                 **cfg.get("amp", {}))
        self.recompute = Strategy._Cfg(enable=False,
                                       **cfg.get("recompute", {}))
        self.pipeline = Strategy._Cfg(enable=False, schedule_mode="1F1B",
                                      micro_batch_size=1,
                                      **cfg.get("pipeline", {}))


class DistModel:
    """Reference: auto_parallel/api.py DistModel — the trainable object
    dist.to_static returns: __call__ runs one step in the current mode
    (train/eval/predict) on the sharded program."""

    def __init__(self, layer, loader=None, loss=None, optimizer=None,
                 strategy=None):
        from .engine import parallelize
        from . import topology as topo_mod
        self._layer = layer
        self._loss = loss
        self._strategy = strategy or Strategy()
        hcg = topo_mod.get_hybrid_communicate_group()
        mesh = hcg.mesh if hcg is not None else None
        stage = (self._strategy.sharding.stage
                 if self._strategy.sharding.enable else 0)
        loss_fn = None
        if loss is not None:
            def loss_fn(m, *batch):
                out = m(*batch[:-1])
                return loss(out, batch[-1])
        self._step = parallelize(
            layer, optimizer, loss_fn=loss_fn, mesh=mesh,
            sharding_stage=2 if stage >= 2 else 0,
            compute_dtype=(self._strategy.amp.dtype
                           if self._strategy.amp.enable else None))
        self._mode = "train"

    def train(self):
        self._mode = "train"
        self._layer.train()

    def eval(self):
        self._mode = "eval"
        self._layer.eval()

    def predict(self):
        self._mode = "predict"
        self._layer.eval()

    def state_dict(self, *a, **k):
        return self._layer.state_dict(*a, **k)

    def dist_main_program(self, mode=None):
        return self._step          # the compiled step IS the program

    def __call__(self, *batch):
        if self._mode == "train":
            return self._step.train_batch(*batch)
        from ..core.dispatch import no_grad
        with no_grad():
            if self._mode == "eval" and self._loss is not None:
                out = self._layer(*batch[:-1])
                return self._loss(out, batch[-1])
            return self._layer(*batch)


def to_static(layer, loader=None, loss=None, optimizer=None,
              strategy=None):
    """Reference: dist.to_static (auto_parallel/api.py) — lift a dygraph
    layer + loss + optimizer into a DistModel over the current mesh."""
    return DistModel(layer, loader=loader, loss=loss, optimizer=optimizer,
                     strategy=strategy)


# -- PS dataset configs ------------------------------------------------------

class CountFilterEntry:
    """Reference: distributed/entry_attr.py CountFilterEntry — admit a
    sparse feature into the table only after `count` shows (maps onto the
    host table's eviction/liveness counters)."""

    def __init__(self, count):
        if count < 0:
            raise ValueError("count must be >= 0")
        self.count = int(count)

    def _to_attr(self):
        return f"count_filter_entry:{self.count}"


class ProbabilityEntry:
    """Reference: entry_attr.py ProbabilityEntry — admit with probability."""

    def __init__(self, probability):
        if not 0.0 <= probability <= 1.0:
            raise ValueError("probability must be in [0, 1]")
        self.probability = float(probability)

    def _to_attr(self):
        return f"probability_entry:{self.probability}"


class ShowClickEntry:
    """Reference: entry_attr.py ShowClickEntry — show/click slot names for
    CTR accessors."""

    def __init__(self, show_slot, click_slot):
        self.show_slot = str(show_slot)
        self.click_slot = str(click_slot)

    def _to_attr(self):
        return f"show_click_entry:{self.show_slot}:{self.click_slot}"


class InMemoryDataset:
    """Reference: distributed/fleet/dataset InMemoryDataset (C++ DataFeed
    ingest). Python-native: slot-record text files load into memory, then
    iterate as (slot_1 ids, ..., label) batches through paddle.io.

    Line format (the reference's slot data feed): whitespace-separated
    `slot:id` tokens plus an optional `label:x` token."""

    def __init__(self):
        self._records = []
        self._filelist = []
        self._slots = []
        self._batch_size = 1

    def init(self, batch_size=1, use_var=None, pipe_command=None,
             **kwargs):
        self._batch_size = int(batch_size)
        self._slots = [getattr(v, "name", str(i))
                       for i, v in enumerate(use_var or [])]

    set_batch_size = init

    def set_filelist(self, filelist):
        self._filelist = list(filelist)

    def load_into_memory(self):
        self._records = []
        for path in self._filelist:
            with open(path) as f:
                for line in f:
                    rec = {}
                    for tok in line.split():
                        k, _, v = tok.partition(":")
                        rec.setdefault(k, []).append(float(v)
                                                     if k == "label"
                                                     else int(v))
                    if rec:
                        self._records.append(rec)

    def local_shuffle(self):
        import random
        random.shuffle(self._records)

    def global_shuffle(self, fleet=None, thread_num=1):
        self.local_shuffle()

    def release_memory(self):
        self._records = []

    def get_memory_data_size(self, fleet=None):
        return len(self._records)

    def __len__(self):
        return len(self._records)

    def __iter__(self):
        slots = self._slots or sorted(
            {k for r in self._records for k in r if k != "label"})
        for r in self._records:
            feats = [np.asarray(r.get(s, [0]), np.int64) for s in slots]
            yield tuple(feats) + (np.float32(r.get("label", [0.0])[0]),)


class QueueDataset(InMemoryDataset):
    """Reference: QueueDataset — streaming variant; here the same reader
    without the in-memory shuffle contract."""

    def load_into_memory(self):  # streaming: files read lazily
        pass

    def __iter__(self):
        for path in self._filelist:
            with open(path) as f:
                for line in f:
                    rec = {}
                    for tok in line.split():
                        k, _, v = tok.partition(":")
                        rec.setdefault(k, []).append(float(v)
                                                     if k == "label"
                                                     else int(v))
                    if not rec:
                        continue
                    slots = self._slots or sorted(
                        k for k in rec if k != "label")
                    feats = [np.asarray(rec.get(s, [0]), np.int64)
                             for s in slots]
                    yield tuple(feats) + (np.float32(
                        rec.get("label", [0.0])[0]),)
