"""Parameter-server workload answer: mesh-sharded embedding training.

Reference analog: the brpc parameter server (fluid/distributed/ps/ —
BrpcPsServer/Client, memory_sparse_table, TheOnePSRuntime the_one_ps.py:
1028) that search/rec workloads use to hold 100B-feature embedding tables
with async sparse push/pull.

TPU-native redesign: there are no parameter servers — the mesh IS the
parameter server. Embedding tables shard their rows across ALL devices
(P over the flattened mesh axes), lookups compile to gathers whose
cross-chip traffic rides ICI (XLA inserts the collective), and "sparse
push" is the scatter-add cotangent of the gather inside the same jitted
train step — synchronous, exact, and overlap-scheduled by the compiler
instead of an async brpc pipeline. Capacity scales with pod HBM
(reference tables scale with host DRAM); the CPU/host tier of the
reference (ssd_sparse_table) maps to host-offloaded tables via
jax.device_put with host memory kinds when needed.
"""
from __future__ import annotations

import collections
import threading

import numpy as np
import jax
import jax.numpy as jnp

from ..core.tensor import Tensor
from ..core.dispatch import apply
from ..nn.layer.layers import Layer
from ..sharding import named_sharding as _named_sharding, spec as _pspec
from .. import nn

__all__ = ["ShardedEmbedding", "DistributedLookupTable",
           "HostOffloadedEmbedding"]


class ShardedEmbedding(Layer):
    """Embedding with rows sharded over mesh axes (default: every axis —
    the whole pod holds one table, like a PS fleet holds one table).

    Use under `distributed.parallelize`: the row dim carries the sharding
    spec; XLA turns the id gather into (gather + collective) on ICI.
    sparse_grad parity: the backward is a scatter-add into the sharded
    rows — only touched rows produce traffic, the SelectedRows analog.
    """

    def __init__(self, num_embeddings, embedding_dim, axes=("mp",),
                 sparse=True, weight_attr=None, scale_grad_by_freq=False):
        super().__init__()
        self.num_embeddings = int(num_embeddings)
        self.embedding_dim = int(embedding_dim)
        std = 1.0 / max(1.0, np.sqrt(embedding_dim))
        self.weight = self.create_parameter(
            [self.num_embeddings, self.embedding_dim], attr=weight_attr,
            default_initializer=nn.initializer.Normal(0.0, std))
        # row-sharded over the given mesh axes (tuple spec shards the row
        # dim over their product)
        self.weight.dist_spec = _pspec(tuple(axes), None)

    def forward(self, ids):
        return apply("sharded_embedding", _lookup_impl,
                     [self.weight, ids], {})


def _lookup_impl(table, ids):
    return jnp.take(table, ids, axis=0)


class AsyncPushCommunicator:
    """Background sparse-push worker with bounded staleness (reference:
    fluid/distributed/ps/service/communicator/communicator.h AsyncCommunicator
    — trainer threads enqueue gradient segments, send threads merge and push,
    `max_merge_var_num`/queue size bound the staleness window).

    TPU-native shape: the dense step (compiled, on-chip) never waits for the
    host-table scatter; pushes ride a queue drained by one worker thread.
    The staleness bound is `max_pending` outstanding pushes — when the queue
    is full the trainer blocks, so a row can be at most `max_pending` pushes
    stale when read. flush() is the barrier (checkpointing, eval)."""

    def __init__(self, apply_fn, max_pending=8):
        self._apply = apply_fn
        self.max_pending = int(max_pending)
        self._q = collections.deque()
        self._cv = threading.Condition()
        self._busy = False
        self._stop = False
        self.pushed = 0          # applied by the worker
        self.enqueued = 0
        self._worker = threading.Thread(target=self._loop, daemon=True)
        self._worker.start()

    def put(self, uids, row_ct):
        with self._cv:
            while len(self._q) >= self.max_pending:   # staleness bound
                self._cv.wait()
            self._q.append((uids, row_ct))
            self.enqueued += 1
            self._cv.notify_all()

    def _loop(self):
        from .. import profiler as _prof
        while True:
            with self._cv:
                while not self._q and not self._stop:
                    self._cv.wait()
                if self._stop and not self._q:
                    return
                uids, row_ct = self._q.popleft()
                self._busy = True
                self._cv.notify_all()
            try:
                with _prof.RecordEvent("ps_async_push"):
                    self._apply(uids, row_ct)
            finally:
                with self._cv:
                    self._busy = False
                    self.pushed += 1
                    self._cv.notify_all()
                from ..core import monitor
                monitor.increment("ps_async_push_total")

    def flush(self):
        """Barrier: wait until every enqueued push has been applied."""
        with self._cv:
            while self._q or self._busy:
                self._cv.wait()

    @property
    def pending(self):
        with self._cv:
            return len(self._q) + (1 if self._busy else 0)

    def shutdown(self):
        with self._cv:
            self._stop = True
            self._cv.notify_all()
        self._worker.join(timeout=5)


class HostOffloadedEmbedding(Layer):
    """Embedding table resident in HOST memory with sparse on-table updates
    and an optional HBM hot-row cache.

    Reference analog: the PS host/SSD table tier —
    paddle/fluid/distributed/ps/table/memory_sparse_table.cc +
    ssd_sparse_table.h, whose capacity is host DRAM/SSD (not accelerator
    memory) and whose optimizer (sgd/adagrad accessors,
    table/sparse_sgd_rule.cc) lives WITH the table, applying per-row
    sparse pushes.

    TPU-native redesign:
    - the table array is placed with the `pinned_host` memory kind (jax
      memories API); lookups compile to a host-space gather of the
      *deduplicated* ids followed by one host->HBM transfer of just the
      touched rows — HBM never holds the table or a dense gradient;
    - the backward pass delivers row cotangents to the table's own sparse
      optimizer (sgd or adagrad), which scatter-updates the host rows in
      place (donated buffer) — the analog of the PS async sparse push,
      made synchronous and compiled;
    - `cache_size` > 0 keeps an LRU cache of hot rows in device memory
      for eval/predict flows (valid because eval never mutates rows).

    The table is NOT a dense Parameter: framework optimizers skip it, the
    table optimizes itself (exactly the reference PS contract where the
    worker optimizer never sees sparse tables).
    """

    def __init__(self, num_embeddings, embedding_dim, optimizer="adagrad",
                 learning_rate=0.05, initializer_range=None, axes=None,
                 cache_size=0, dtype=jnp.float32, async_push=False,
                 max_pending=8):
        super().__init__()
        self.num_embeddings = int(num_embeddings)
        self.embedding_dim = int(embedding_dim)
        self.optimizer = optimizer
        self.learning_rate = float(learning_rate)
        self.cache_size = int(cache_size)
        if optimizer not in ("sgd", "adagrad"):
            raise ValueError("optimizer must be 'sgd' or 'adagrad'")

        std = (initializer_range if initializer_range is not None
               else 1.0 / max(1.0, np.sqrt(embedding_dim)))
        init = np.random.normal(
            0.0, std, (self.num_embeddings, self.embedding_dim)).astype(
                np.dtype(dtype))
        self._host_sharding, self._dev_sharding = self._shardings(axes)
        table = jax.device_put(init, self._host_sharding)
        self.weight = Tensor(table, stop_gradient=True)
        if optimizer == "adagrad":
            self._accum = jax.device_put(
                np.zeros((self.num_embeddings,), np.float32),
                self._acc_host_sharding)
        else:
            self._accum = None
        # LRU cache state (eval only): id -> slot, plus the HBM row store
        self._cache_rows = None
        self._cache_map = {}
        self._cache_clock = []
        self._push_probe = None
        # async communicator (reference communicator.h semantics)
        self._lock = threading.Lock()
        self._comm = AsyncPushCommunicator(
            self._apply_push_sync, max_pending) if async_push else None
        # per-row liveness for the eviction/TTL story (reference
        # memory_sparse_table shrink): step counter + last-touched step
        self._step = 0
        self._last_seen = np.zeros((self.num_embeddings,), np.int64)

    def _shardings(self, axes):
        from . import topology as topo_mod

        hcg = topo_mod.get_hybrid_communicate_group()
        if axes and hcg is not None:
            mesh = hcg.mesh
            host = _named_sharding(mesh, (tuple(axes), None)) \
                .with_memory_kind("pinned_host")
            dev = _named_sharding(mesh, ()).with_memory_kind("device")
            self._acc_host_sharding = _named_sharding(
                mesh, (tuple(axes),)).with_memory_kind("pinned_host")
        else:
            d = jax.devices()[0]
            host = jax.sharding.SingleDeviceSharding(
                d, memory_kind="pinned_host")
            dev = jax.sharding.SingleDeviceSharding(d, memory_kind="device")
            self._acc_host_sharding = host
        return host, dev

    # -- compiled host-space kernels ------------------------------------
    def _pull_fn(self):
        host, dev = self._host_sharding, self._dev_sharding

        def pull(table, uids):
            uh = jax.device_put(uids, host)
            rows = table.at[uh].get(mode="promise_in_bounds")
            return jax.device_put(rows, dev)

        return jax.jit(pull)

    def _push_fn(self):
        """Compiled host-space scatter update (the TPU path: table rows
        update IN host memory, only cotangents transit HBM)."""
        host = self._host_sharding
        acc_host = self._acc_host_sharding
        opt = self.optimizer

        def push(table, accum, uids, ct, lr):
            # pads duplicate a live id with ZERO cotangent, so every write
            # must be scatter-ADD (duplicate .set has an unspecified winner
            # and can drop the real update)
            uh = jax.device_put(uids, host)
            ct_h = jax.device_put(ct, host)
            lr_h = jax.device_put(lr, host)
            if opt == "adagrad":
                g2 = jnp.sum(ct_h * ct_h, axis=-1)
                accum = accum.at[uh].add(g2, mode="promise_in_bounds")
                acc_rows = accum.at[uh].get(mode="promise_in_bounds")
                scale = (lr_h / jnp.sqrt(acc_rows + 1e-10))[:, None]
            else:
                scale = lr_h
            table = table.at[uh].add(-scale * ct_h,
                                     mode="promise_in_bounds")
            return table, accum

        return jax.jit(push, donate_argnums=(0, 1),
                       out_shardings=(host, acc_host))

    def _host_push_works(self):
        """Probe once whether XLA can execute host-space scatter on this
        backend (TPU: yes; CPU runtime lacks the Host
        annotate_device_placement custom call)."""
        if self._push_probe is None:
            try:
                probe_tab = jax.device_put(
                    np.zeros((2, self.embedding_dim), np.float32),
                    self._host_sharding)
                probe_acc = jax.device_put(np.zeros((2,), np.float32),
                                           self._acc_host_sharding)
                t, a = self._push(probe_tab, probe_acc,
                                  jnp.zeros((1,), jnp.int32),
                                  jnp.zeros((1, self.embedding_dim)),
                                  jnp.float32(0.0))
                jax.block_until_ready(t)
                self._push_probe = True
            except Exception:  # tpu-lint: disable=TL007 — capability
                # probe: ANY failure means "no device push path here"
                self._push_probe = False
        return self._push_probe

    def _numpy_push(self, uids, row_ct):
        """Fallback sparse push: row updates via a host->host numpy pass.
        Capacity-equivalent (the table never touches device memory); the
        full-table host memcpy it costs is what the compiled host-space
        path above removes on TPU."""
        tab = np.array(self.weight._value)
        ids = np.asarray(uids)
        ct = np.asarray(row_ct, tab.dtype)
        if self.optimizer == "adagrad":
            acc = np.array(self._accum)
            g2 = np.sum(np.asarray(row_ct, np.float32) ** 2, axis=-1)
            np.add.at(acc, ids, g2)  # add-per-occurrence: pads add zero
            scale = (self.learning_rate
                     / np.sqrt(acc[ids] + 1e-10))[:, None]
            self._accum = jax.device_put(acc, self._acc_host_sharding)
        else:
            scale = self.learning_rate
        np.subtract.at(tab, ids, (scale * ct).astype(tab.dtype))
        self.weight._value = jax.device_put(tab, self._host_sharding)

    def forward(self, ids):
        flat = ids._value.reshape(-1) if isinstance(ids, Tensor) \
            else jnp.asarray(ids).reshape(-1)
        orig_shape = tuple(ids.shape)
        if not self.training and self.cache_size > 0:
            rows = self._cached_lookup(np.asarray(flat))
            out = rows.reshape(orig_shape + (self.embedding_dim,))
            return Tensor(out)
        # real host-side dedup (the forward is eager, so dynamic-size unique
        # is fine); pad the unique set to the next power of two so the pull/
        # push jits see a bounded set of shapes instead of one per count
        uids_np, inv_np = np.unique(np.asarray(flat), return_inverse=True)
        n_u = len(uids_np)
        padded = 1 << (n_u - 1).bit_length() if n_u > 1 else 1
        uids_np = np.concatenate(
            [uids_np, np.full(padded - n_u, uids_np[0], uids_np.dtype)])
        uids = jnp.asarray(uids_np.astype(np.int32))
        inv = jnp.asarray(inv_np.astype(np.int32))
        if not hasattr(self, "_pull"):
            self._pull = self._pull_fn()
            self._push = self._push_fn()
        with self._lock:
            table_ref = self.weight._value   # consistent snapshot vs worker
        rows_u = self._pull(table_ref, uids)
        rows = rows_u[inv].reshape(orig_shape + (self.embedding_dim,))
        out = Tensor(rows, stop_gradient=not self.training)
        if self.training:
            out._grad_node = _SparsePushNode(self, uids, inv, orig_shape)
            out._out_idx = 0
        return out

    def _apply_push(self, uids, row_ct):
        """Sparse push entry. Sync mode applies inline; async mode enqueues
        and returns — the dense step proceeds while the worker thread
        scatters into the host table (bounded staleness)."""
        self._step += 1
        self._last_seen[np.asarray(uids)] = self._step
        if self._comm is not None:
            self._comm.put(uids, row_ct)
            return
        self._apply_push_sync(uids, row_ct)

    def flush(self):
        """Drain pending async pushes (call before eval/checkpoint)."""
        if self._comm is not None:
            self._comm.flush()

    def evict_stale(self, max_age):
        """TTL eviction (reference: memory_sparse_table.cc shrink / SSD
        tier demotion): rows untouched for `max_age` pushes are reset to
        fresh init values and their optimizer state cleared — bounding the
        effective hot set the way the reference bounds table growth."""
        self.flush()
        with self._lock:
            stale = np.nonzero((self._step - self._last_seen)
                               > int(max_age))[0]
            if len(stale) == 0:
                return 0
            tab = np.array(self.weight._value)
            std = 1.0 / max(1.0, np.sqrt(self.embedding_dim))
            tab[stale] = np.random.normal(
                0.0, std, (len(stale), self.embedding_dim)).astype(tab.dtype)
            self.weight._value = jax.device_put(tab, self._host_sharding)
            if self._accum is not None:
                acc = np.array(self._accum)
                acc[stale] = 0.0
                self._accum = jax.device_put(acc, self._acc_host_sharding)
            self._cache_map.clear()
            self._cache_clock.clear()
            return int(len(stale))

    def _apply_push_sync(self, uids, row_ct):
        """Sparse push: table's own optimizer updates touched rows."""
        with self._lock:
            self._apply_push_locked(uids, row_ct)

    def _apply_push_locked(self, uids, row_ct):
        if self._host_push_works():
            acc = self._accum if self._accum is not None else \
                jax.device_put(np.zeros((1,), np.float32),
                               self._acc_host_sharding)
            new_table, new_acc = self._push(
                self.weight._value, acc, uids, row_ct,
                jnp.float32(self.learning_rate))
            self.weight._value = new_table
            if self._accum is not None:
                self._accum = new_acc
        else:
            self._numpy_push(uids, row_ct)
        self._cache_map.clear()  # rows changed: invalidate the HBM cache
        self._cache_clock.clear()

    # -- eval-time HBM hot-row cache ------------------------------------
    def _cached_lookup(self, flat_np):
        if self._cache_rows is None:
            self._cache_rows = jnp.zeros(
                (self.cache_size, self.embedding_dim),
                self.weight._value.dtype)
        uniq = np.unique(flat_np)
        if len(uniq) > self.cache_size:
            # working set exceeds the cache: serve this batch directly from
            # the host table, leave the cache untouched
            if not hasattr(self, "_pull"):
                self._pull = self._pull_fn()
                self._push = self._push_fn()
            return self._pull(self.weight._value,
                              jnp.asarray(flat_np, jnp.int32))
        # LRU-touch this batch's hits FIRST so the miss-fill below can never
        # evict a row the same batch still needs
        for rid in uniq:
            rid = int(rid)
            if rid in self._cache_map:
                self._cache_clock.remove(rid)
                self._cache_clock.append(rid)
        missing = [int(i) for i in uniq if int(i) not in self._cache_map]
        if missing:
            if not hasattr(self, "_pull"):
                self._pull = self._pull_fn()
                self._push = self._push_fn()
            rows = self._pull(self.weight._value,
                              jnp.asarray(missing, jnp.int32))
            for k, rid in enumerate(missing):
                if len(self._cache_map) >= self.cache_size:
                    evict = self._cache_clock.pop(0)
                    slot = self._cache_map.pop(evict)
                else:
                    slot = len(self._cache_map)
                self._cache_map[rid] = slot
                self._cache_clock.append(rid)
                self._cache_rows = self._cache_rows.at[slot].set(rows[k])
        slots = np.asarray([self._cache_map[int(i)] for i in flat_np],
                           np.int32)
        return self._cache_rows[jnp.asarray(slots)]

    @property
    def memory_kind(self):
        return self.weight._value.sharding.memory_kind


class _SparsePushNode:
    """Tape node delivering row cotangents to the table's sparse optimizer
    (the PS 'push_sparse' analog, fluid/distributed/ps/service/
    brpc_ps_client.cc push_sparse)."""

    def __init__(self, table, uids, inv, ids_shape):
        from ..core.dispatch import GradNode
        self.name = "host_table_push"
        self.impl = None
        self.statics = {}
        self.statics_key = ()
        self.input_arrays = []
        self.input_metas = []
        self.n_outputs = 1
        self.out_is_seq = False
        self._table = table
        self._uids = uids
        self._inv = inv
        self._ids_shape = ids_shape
        GradNode._counter[0] += 1
        self._id = GradNode._counter[0]

    def run_vjp_taped(self, cotangents):
        # push_sparse is a side effect (host-table optimizer apply), not a
        # differentiable op; under create_graph the push still happens and
        # no second-order graph exists past the table (input_metas is []).
        from ..core.tensor import Tensor
        return self.run_vjp(
            [c._value if isinstance(c, Tensor) else c for c in cotangents])

    def run_vjp(self, cotangents):
        ct = cotangents[0]
        dim = self._table.embedding_dim
        flat_ct = ct.reshape(-1, dim)
        # fold duplicate ids: segment-sum cotangents onto unique rows
        row_ct = jax.ops.segment_sum(
            flat_ct, self._inv, num_segments=self._uids.shape[0])
        self._table._apply_push(self._uids, row_ct)
        return []

    def release(self):
        pass


class DistributedLookupTable(Layer):
    """Multi-slot lookup (reference: the PS pull_sparse over slots +
    fused embedding): one shared table, a list of id slots, concatenated
    slot embeddings out — the rec-model front end."""

    def __init__(self, num_embeddings, embedding_dim, num_slots,
                 axes=("mp",)):
        super().__init__()
        self.embedding = ShardedEmbedding(num_embeddings, embedding_dim,
                                          axes=axes)
        self.num_slots = int(num_slots)

    def forward(self, slot_ids):
        """slot_ids: [batch, num_slots] int -> [batch, num_slots*dim]."""
        emb = self.embedding(slot_ids)  # [b, slots, dim]
        return emb.reshape([emb.shape[0], -1])


# ---------------------------------------------------------------------------
# CTR accessor + cross-process PS service (round 4)
# ---------------------------------------------------------------------------


class CtrAccessorConfig:
    """Reference: the ctr_accessor_param proto consumed by
    paddle/fluid/distributed/ps/table/ctr_accessor.cc:37."""

    def __init__(self, nonclk_coeff=0.1, click_coeff=1.0,
                 show_click_decay_rate=0.98, delete_threshold=0.8,
                 delete_after_unseen_days=30, embedx_threshold=10.0):
        self.nonclk_coeff = float(nonclk_coeff)
        self.click_coeff = float(click_coeff)
        self.show_click_decay_rate = float(show_click_decay_rate)
        self.delete_threshold = float(delete_threshold)
        self.delete_after_unseen_days = float(delete_after_unseen_days)
        self.embedx_threshold = float(embedx_threshold)


class CtrAccessor:
    """Per-feature CTR scoring/lifecycle (reference:
    ps/table/ctr_accessor.h:30, .cc — CtrCommonFeatureValue carries
    show/click/unseen_days; Shrink() time-decays then deletes by score;
    NeedExtendMF() gates the wide embedx vector on the same score).

    TPU-native: the accessor is a numpy-side policy object attached to a
    host table — scoring math matches the reference exactly; storage stays
    columnar (dict of arrays) instead of packed float rows."""

    def __init__(self, config=None):
        self.cfg = config or CtrAccessorConfig()
        self.show = {}          # uid -> float
        self.click = {}
        self.unseen_days = {}

    def show_click_score(self, show, click):
        """Reference ctr_accessor.cc:305: (show-click)*nonclk + click*clk."""
        c = self.cfg
        return (show - click) * c.nonclk_coeff + click * c.click_coeff

    def update(self, uids, shows, clicks):
        """Push-side stat fold (CtrCommonPushValue merge): accumulate
        show/click and reset unseen_days for the touched rows. Aging is a
        separate daily pass (age_days) like the reference — doing it per
        push would both cost O(table) per batch and count batches as
        days."""
        for u, s, k in zip(np.asarray(uids).tolist(),
                           np.asarray(shows).tolist(),
                           np.asarray(clicks).tolist()):
            self.show[u] = self.show.get(u, 0.0) + float(s)
            self.click[u] = self.click.get(u, 0.0) + float(k)
            self.unseen_days[u] = 0.0

    def age_days(self, days=1.0):
        """Daily aging pass (reference: unseen_days accrues per day and is
        consumed by Shrink)."""
        for u in self.show:
            self.unseen_days[u] = self.unseen_days.get(u, 0.0) + days

    def score(self, uid):
        return self.show_click_score(self.show.get(uid, 0.0),
                                     self.click.get(uid, 0.0))

    def need_extend_mf(self, uid):
        """Reference ctr_accessor.cc:190 NeedExtendMF: grow the wide
        embedx vector only once the feature's score crosses the
        threshold."""
        return self.score(uid) >= self.cfg.embedx_threshold

    def shrink(self):
        """Reference ctr_accessor.cc:62 Shrink: decay show/click first,
        then delete rows whose score fell below delete_threshold or that
        were unseen too long. Returns the deleted uids."""
        c = self.cfg
        dead = []
        for u in list(self.show):
            self.show[u] *= c.show_click_decay_rate
            self.click[u] *= c.show_click_decay_rate
            if (self.show_click_score(self.show[u], self.click[u])
                    < c.delete_threshold
                    or self.unseen_days.get(u, 0.0)
                    > c.delete_after_unseen_days):
                dead.append(u)
                self.show.pop(u, None)
                self.click.pop(u, None)
                self.unseen_days.pop(u, None)
        return dead


# -- cross-process push: workers send sparse grads to the owner process ----

_PS_TABLES: dict = {}
# rpc's SAME-PROCESS fast path runs each call on its own thread; pushes
# must serialize like the cross-process serve loop does naturally
_PS_LOCK = threading.Lock()


def host_ps_table(name, table, accessor=None):
    """Owner-process side: register a HostOffloadedEmbedding (or any object
    with _apply_push(uids, row_ct)) under `name` so remote workers can push
    to it via dist.rpc (reference: the brpc PsService hosting tables,
    ps/service/brpc_ps_server.h)."""
    _PS_TABLES[name] = (table, accessor)
    return table


def _ps_remote_push(name, uids, row_ct, shows=None, clicks=None):
    """Runs in the OWNER process via rpc: apply a sparse push (and CTR
    stats when provided). Module-level so rpc can pickle the reference."""
    with _PS_LOCK:
        table, accessor = _PS_TABLES[name]
        table._apply_push(jnp.asarray(np.asarray(uids)),
                          jnp.asarray(np.asarray(row_ct)))
        if accessor is not None and shows is not None:
            accessor.update(uids, clicks=clicks, shows=shows)
    return True


def _ps_remote_pull(name, uids):
    table, _ = _PS_TABLES[name]
    rows = np.asarray(table.weight._value)[np.asarray(uids)]
    return rows


class RemoteCommunicator:
    """Worker-process side: async sparse push to the owner's table over
    dist.rpc with bounded staleness (reference: the cross-node
    AsyncCommunicator, ps/service/communicator/communicator.h:427 — send
    queues bounded by max_merge/independent thread; here jax/numpy grads
    ride the native-store rpc channel and at most `max_pending` pushes may
    be in flight before the caller blocks)."""

    def __init__(self, owner, table_name, max_pending=8):
        self.owner = owner
        self.table_name = table_name
        self.max_pending = int(max_pending)
        self._futs = []

    def push(self, uids, row_ct, shows=None, clicks=None):
        from . import rpc as _rpc
        while len(self._futs) >= self.max_pending:
            self._futs.pop(0).wait(timeout=120)
        fut = _rpc.rpc_async(
            self.owner, _ps_remote_push,
            args=(self.table_name, np.asarray(uids),
                  np.asarray(row_ct),
                  None if shows is None else np.asarray(shows),
                  None if clicks is None else np.asarray(clicks)))
        self._futs.append(fut)
        return fut

    def pull(self, uids):
        from . import rpc as _rpc
        return _rpc.rpc_sync(self.owner, _ps_remote_pull,
                             args=(self.table_name, np.asarray(uids)),
                             timeout=120)

    def flush(self):
        while self._futs:
            self._futs.pop(0).wait(timeout=120)

    @property
    def pending(self):
        self._futs = [f for f in self._futs if not f.done()]
        return len(self._futs)
