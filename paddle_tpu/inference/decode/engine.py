"""paddle_tpu.inference.decode.engine — continuous-batching LLM decode.

`DynamicBatcher` (batching.py) batches at *request* granularity: a formed
batch runs one exported program end-to-end, so a generation workload
would pay head-of-line blocking — every sequence in the batch decodes for
as long as the longest one, and a late arrival waits for the whole batch
to drain. Decode is memory-bound (docs/decode_perf.md: bandwidth_frac
<= 0.53 at the bench shapes), so those wasted iterations are wasted HBM
streaming. The fix is *iteration-level* scheduling in the style of Orca
(OSDI '22) and vLLM/PagedAttention (SOSP '23), composed here from parts
that already exist in-tree:

* **Paged KV cache** (`block_pool.BlockKVCache`): one device-resident
  pool of fixed-size blocks per layer; each sequence holds a block table
  and grows block-by-block, returning blocks the moment it finishes.
  Supports the bf16 and int8 (`cache_quant="int8"`) layouts of
  `GPTForCausalLM.init_cache` via `init_block_pool`. A pool tensor is
  `[N, block_size, Hkv*D]` — flat rows, which the chip stores as they
  are; the engine gathers, scatters and copies whole rows and knows
  nothing of heads (the model views a row as `[Hkv, D]`).

* **Prefill/decode separation with chunked prefill** (Sarathi-Serve,
  OSDI '24): a new sequence's prompt is prefilled in block-aligned
  chunks — one chunk per scheduler round, interleaved with the running
  batch's decode steps, shortest-remaining prompt first — so a long
  prompt never stalls running sequences for a monolithic prefill and a
  short prompt never queues behind one. The sequence joins the RUNNING
  decode batch at the step boundary after its last chunk. Finished /
  cancelled / deadline-expired sequences leave at step boundaries,
  freeing both their batch slot and their blocks.

* **Copy-on-write prefix sharing** (the vLLM move): completed prefills
  publish their prompt KV blocks into a prefix cache keyed by token
  content (full-prompt entries plus every chunk boundary). `submit()`
  matches the longest cached prefix and bumps block REFCOUNTS instead
  of re-prefilling those tokens — N sequences over one system prompt
  hold ONE physical copy of the shared blocks, multiplying effective
  KV capacity and admission headroom. A sequence that must write into
  a shared block (its first private token lands mid-block) COW-copies
  that one block first. Cache entries are LRU-evicted under admission
  pressure; sharing is bit-exact because chunk boundaries are absolute,
  so a reused prefix was computed by the IDENTICAL dispatches the new
  sequence would have run itself.

* **Speculative decoding** (Leviathan et al. 2023): a small DRAFT model
  (`draft_model=`, `speculate_k=K`) autoregressively proposes K tokens
  per scheduler round from its own paged KV state (one compiled
  K-step dispatch), then the TARGET model scores all K+1 positions in
  ONE bucketed verification dispatch. Greedy verification accepts the
  longest prefix where draft argmax == target argmax and commits the
  accepted tokens plus the target's one correction (or bonus) token;
  the draft's KV for rejected positions is rolled back positionally
  (rows past the committed position are rewritten before they can ever
  be attended — the same garbage-row argument chunked prefill makes).
  Decode is memory-bound (bandwidth_frac <= 0.53), so verifying K
  tokens under one streaming of the target weights is nearly free
  throughput. The verify step is a `lax.scan` over sequences of the
  per-position decode body, the same equations the plain decode step
  batches, so the target's argmax at every verified position is that
  of sequential greedy decode within the determinism contract below
  (bit-identical on the CPU backend, where tier-1 observes speculative
  output equal to `speculate_k=0` at every bucket size, int8 KV and
  prefix sharing included). Draft and target each own a refcounted
  `BlockKVCache` (same conservation law; COW rules unchanged), and
  admission reserves the draft's worst-case blocks alongside the
  target's.

* **Bucketed AOT step executables** (`jit/aot.compile_jit`): the decode
  step is compiled once per batch-size bucket and persisted in the
  shared on-disk `CompileCache`, so a warm process start compiles ZERO
  decode-step executables. Each step is a single gathered dispatch: the
  compiled program reads every sequence's KV through its block table
  (XLA gather — the portable path; the TPU-native read-through-the-
  table kernel is `ops/pallas/decode_attn.paged_decode_attention`).

* **Streaming through the serving runtime** (`serving.ServingPool`):
  every dispatch runs as a request on an internal supervised pool, so a
  wedged decode step trips the pool's EXISTING hang detection (the
  wedged worker is retired, capacity restored, and the step is
  re-dispatched from the pool as it then stands). Sequence
  admission reuses the serving runtime's typed semantics: bounded
  waiting queue (`Overloaded`), per-sequence monotonic deadlines
  covering queue wait + generation (`DeadlineExceeded`), `PoolClosed`
  after shutdown, and `RequestFailed` for execution faults. A failing
  sequence is evicted ALONE — a failed multi-sequence step is re-run as
  isolated single-sequence steps to pin the blame, mirroring the
  batcher's split-on-failure.

* **One copy of the cache, consumed by every dispatch.** Every program
  that writes the pool (decode step, block-diffusion step, prompt chunk,
  verify, propose, draft catch-up, COW copy, slot zeroing) takes it
  DONATED: the output pool is the input's buffers, updated in place, so a
  dispatch neither copies the pool in and out of HBM nor holds two of
  them. The pool is therefore single-owner: `pool.tensors` is read at the
  last moment before the compiled call, by the step-pool worker that is
  about to make it, and replaced by the program's output when the call
  has come back. The fault contract: (a) a dispatch that raises before
  the compiled call (every `fault_hook` phase, an argument check) leaves
  the pool whole, and is retried or isolated as ever; (b) a retired
  worker never dispatches: an attempt that the hang detection gave up on
  is cancelled under the engine's lock, where a worker also takes the
  pool, and the re-submission reads the pool afresh (an attempt that
  timed out inside its compiled call has the pool and is waited for
  instead: what it brings back, late, is the step); (c) a compiled call
  that fails AFTER its buffers were consumed (a pool leaf `is_deleted()`)
  costs the cache, not the sequences: the engine allocates a fresh pool
  and state slots, drops every table, and sends every resident sequence
  back through admission to be prefilled again from its committed tokens
  (prompt + delivered output, the resume path), counted in
  `stats()["pool_rebuilds"]`. No delivered token changes. The members of
  the failed dispatch then decode alone until a step of their own has
  come back, and one that fails alone again is the one that fails.

* **Generation by diffusion over blocks** (`block_diffusion=`, off by
  default; SDAR-class models trained under a mask that is causal over
  blocks of B positions and full inside one): a sequence's answer grows a
  block of B tokens at a time. A block starts as B mask tokens; each
  DENOISING pass forwards its B positions against the cache of all earlier
  blocks, writes no cache, and fixes the B/T masked positions whose
  arg-max token is most confident; once all are fixed one COMMIT pass
  forwards the block again and writes its B cache rows. A decode dispatch
  is then one `[1, B]` forward a sequence at its block's offset, the
  bucket's forwards batched into one (`_bd_fn`), each sequence in its own
  phase — the phase is DATA (one executable a bucket, the cache write
  selected by it), the block's state lives with the
  sequence between rounds and changes only after a dispatch has returned,
  and the stream gets a block's tokens when it commits, each with the
  pass that fixed it (`SequenceStream.passes`).

* **Recurrent layers beside attention** (a model whose configuration gives
  some layers the gated delta rule, `models/linear_attention.py`): such a
  layer's cache is a state of fixed size, not a row a position. The pool
  holds it as one SLOT a sequence (`block_pool`): given and zeroed when the
  sequence is admitted, read and written by slot where an attention layer's
  rows go by block table (`_gather`, `_scatter_new_rows`, the prefill's
  scatter), handed from one prompt chunk to the next with the convolution's
  window, and returned when the sequence ends. A prompt chunk tells the
  model how many of its bucket's positions are real: padding that rows
  tolerate would corrupt a state. What assumes a cache made of rows (the
  prefix cache, copy-on-write, speculation, block diffusion, int8 KV, a
  mesh, adapters) is refused at construction (`_check_recurrent`).

Determinism contract: the plain decode step runs the active batch through
ONE batched forward (`_forward_bucket`: the model's per-sequence cached
step traced once and batched by `vmap`, so a weight crosses HBM once a
step, not once a sequence). What that promises: a fixed batch composition
gives the same tokens and the same pool bytes run after run; a sequence's
rows are written by its own slot and by no other (padded slots write
reserved block 0); and a sequence's logits agree with the float32
reference within the tolerance the benchmark enforces (`token_gap` <= 0.1
where the float8 control reads 0.18-0.40; `benchmarks/limits/`). What it
no longer promises BY THE PROGRAM'S SHAPE is bit-equality of a sequence's
tokens across bucket sizes: the per-sequence equations are the same, but
a backend may sum a batched matmul's rows in another order than the same
row alone. The tier-1 tests still observe that equality on the CPU
backend (solo vs batched, int8 KV, prefix sharing), and
`tests/test_decode_batched_step.py` holds the logits of a row in a bucket
to a written tolerance of the row alone, which is what remains true on a
backend that is not row-stable. `_run_isolated` (single-sequence re-runs
after a failed step) and the speculative verify both work from committed
state as before; their tokens equal the batched step's by that same
tolerance, not by construction. The block-diffusion step (`_bd_fn`) is
batched the same way and promises the same: a fixed composition repeats
itself, pool bytes included; a block's rows are written by its own slot's
commit pass and by nothing else (denoising passes and padded slots write
reserved block 0); and a sequence's tokens agree with the float32
reference within the benchmark's tolerance (`token_gap` <= 0.005 where
the float8 control reads 0.02-0.04, `pick_gap` <= 0.02). Across bucket
sizes even the order of a sequence's sums differs there: a sparse-expert
layer accumulates a position's experts in expert order where the
dispatch has more assignments than the layer has experts, else in
assignment order (`models/moe.py`), both in float32;
`tests/test_sdar_block_diffusion.py` holds a sequence in a bucket of 16
to a written tolerance of the sequence alone. The speculative verify /
propose steps keep their `lax.scan` over per-sequence sub-steps (they
accept and reject in sequence, and no cell measures them). Greedy
decoding (argmax) is the deterministic mode the equality and
fault-isolation invariants are tested over.

Usage::

    engine = DecodeEngine(model, max_length=256, block_size=16)
    stream = engine.submit(prompt_ids, max_new_tokens=64, timeout=5.0)
    for tok in stream:          # tokens stream out as they are decoded
        ...
    engine.shutdown()

or through a `ServingPool(..., decode_engine=engine)` via
`pool.submit_generate(...)`. See docs/llm_serving.md.
"""
from __future__ import annotations

import collections
import concurrent.futures
import hashlib
import itertools
import logging
import math
import os
import queue
import statistics
import threading
import time

import numpy as np

from ...analysis import commcheck as _cc
from ...analysis import locks as _locks
from ...analysis import graphcheck as _gc
from ...analysis import runtime_san as _san
from ...obs import flight as _flight
from ...obs import trace as _otrace
from ..serving import (AdapterNotLoaded, Deadline, DeadlineExceeded,
                       Overloaded, PoolClosed, RequestFailed, RetryPolicy,
                       ServingPool, _NullPredictor)
from .block_pool import BlockKVCache, OutOfBlocks, RESERVED_BLOCKS

__all__ = ["DecodeEngine", "SequenceStream"]


# sequence lifecycle
_WAITING, _PREFILL, _ACTIVE, _DONE = "waiting", "prefill", "active", "done"

#: reference-owner tag for blocks pinned by the engine's prefix cache
_CACHE_OWNER = "prefix-cache"

_END = object()   # stream sentinel

_log = logging.getLogger(__name__)

# scheduler-phase spans (docs/observability.md, "Reading an idle gap"):
# every loop iteration that finds work is one `decode.round` root and
# these phases tile it. `.enqueue` and `.fetch` run on the step-pool
# worker as children of the scheduler's `.handoff`, whose own time is
# therefore the hand-off itself.
_ROUND = "decode.round"
_IDLE = "decode.idle_wait"
_ADMIT = _ROUND + ".admit"


def _phase_names(kind):
    base = f"{_ROUND}.{kind}"
    return {"round": base, **{p: f"{base}.{p}" for p in (
        "grow", "pack", "handoff", "enqueue", "fetch", "deliver")}}


_PREFILL, _DECODE = _phase_names("prefill"), _phase_names("decode")

#: a round (or an idle wait with work queued) is SLOW past this many
#: seconds AND this many times the median of the last rounds
_SLOW_ROUND_S = 1.0
_SLOW_ROUND_X = 4.0
_SLOW_ROUND_HISTORY = 64


class SequenceStream:
    """Per-sequence streaming handle returned by `DecodeEngine.submit`.

    Iterate to receive tokens as they are decoded; iteration ends with
    `StopIteration` on completion or raises the sequence's typed serving
    error (`DeadlineExceeded` / `RequestFailed` / `PoolClosed`). The
    deadline is enforced on the CALLER side too, so a consumer is
    released at the deadline even if the engine is wedged. Tokens
    delivered so far are always available as `.tokens` (including after
    a failure — partial output is real output)."""

    def __init__(self, seq_id, deadline):
        self.id = seq_id
        self.deadline = deadline
        self.tokens = []          # delivered tokens (engine-appended)
        self.passes = []          # block diffusion: per token, the pass of
        #                           its block that fixed it (else empty)
        self._q = queue.Queue()
        self._status = "running"  # running|completed|failed|timed_out|cancelled
        self._error = None
        self._cancel = None       # engine-installed cancel callback
        self._raised = False
        self._ended = False       # poll() consumed the _END sentinel

    # -- engine side -------------------------------------------------------
    def _push(self, tok):
        self.tokens.append(tok)
        self._q.put(tok)

    def _finish(self, status, error=None):
        self._status = status
        self._error = error
        self._q.put(_END)

    # -- caller side -------------------------------------------------------
    @property
    def status(self):
        return self._status

    def done(self):
        return self._status != "running"

    def cancel(self):
        """Ask the engine to evict this sequence at the next step
        boundary (its blocks return to the pool; batchmates continue)."""
        if self._cancel is not None:
            self._cancel()

    def __iter__(self):
        return self

    def __next__(self):
        if self._raised:
            raise StopIteration
        limit = self.deadline.remaining()
        try:
            if limit is not None and limit <= 0:
                item = self._q.get_nowait()   # already-delivered beats DOA
            else:
                item = self._q.get(timeout=limit)
        except queue.Empty:
            self._raised = True
            raise DeadlineExceeded(
                f"sequence {self.id} exceeded its deadline while "
                f"waiting for the next token") from None
        if item is not _END:
            return item
        self._raised = True
        if self._status == "completed":
            raise StopIteration
        raise self._error

    def result(self, with_passes=False):
        """Drain the stream to completion and return the full generated
        token list; raises the typed error on failure (partial tokens
        stay readable via `.tokens`). `with_passes=True` returns
        `(tokens, passes)`: under block diffusion each token's denoising
        pass (1-based) within its block."""
        for _ in self:
            pass
        if with_passes:
            return list(self.tokens), list(self.passes)
        return list(self.tokens)

    def poll(self, timeout=None):
        """Non-raising pump primitive (the router's streaming proxy and
        the store-transport frame pump consume through this): wait up to
        `timeout` seconds for the next event and return one of

        * ``("tok", token)`` — the next generated token,
        * ``("end", status, error)`` — terminal (re-returned on every
          later call: an end is sticky),
        * ``("empty", None)`` — nothing arrived within `timeout`.

        Unlike iteration, `poll` does NOT enforce the caller-side
        deadline — pumps own their scheduling. A stream must be consumed
        through either the iterator or `poll`, never both."""
        if self._ended:
            return ("end", self._status, self._error)
        try:
            if timeout is None or timeout <= 0:
                item = self._q.get_nowait()
            else:
                item = self._q.get(timeout=timeout)
        except queue.Empty:
            return ("empty", None)
        if item is not _END:
            return ("tok", item)
        self._ended = True
        return ("end", self._status, self._error)


class _Seq:
    __slots__ = ("id", "prompt", "max_new", "deadline", "stream", "state",
                 "blocks", "reserved_total", "outstanding", "pos",
                 "prefill_pos", "matched_tokens", "last_token", "generated",
                 "cancelled", "submitted_at", "span", "draft_blocks",
                 "draft_pos", "draft_outstanding", "spec_proposed",
                 "spec_accepted", "sampling", "adapter", "adapter_slot",
                 "adapter_sig", "sample_base", "out_tokens", "held",
                 "t_submit", "t_admit", "t_first", "round_admit", "chunks",
                 "prefill_ids", "bd", "slot", "suspect")

    def __init__(self, sid, prompt, max_new, deadline):
        self.id = sid
        self.prompt = prompt           # np.int32 [prompt_len]
        self.prefill_ids = prompt      # what prefill puts in the cache (block
        #                                diffusion: the whole blocks of it)
        self.bd = None                 # block diffusion: the open block
        self.slot = 0                  # recurrent layers: its state's slot
        self.suspect = False           # was in a dispatch that failed after
        #                                it consumed the pool: decodes alone
        self.max_new = max_new
        self.deadline = deadline
        self.stream = SequenceStream(sid, deadline)
        self.state = _WAITING
        self.blocks = []               # pool block ids, table order
        self.reserved_total = 0        # worst-case FRESH blocks (admission)
        self.outstanding = 0           # fresh allocations still to come
        self.pos = 0                   # cache position of last_token
        self.prefill_pos = 0           # prompt tokens already in the cache
        self.matched_tokens = 0        # prefix-cache hit length (tokens)
        self.last_token = None
        self.generated = 0
        self.cancelled = False
        self.submitted_at = None       # admission stamp (TTFT histogram)
        self.span = _otrace.null_span()  # sequence root (obs.trace)
        # speculative decoding (draft model) bookkeeping
        self.draft_blocks = []         # draft-pool block ids, table order
        self.draft_pos = 0             # valid draft KV rows (rollback line)
        self.draft_outstanding = 0     # draft fresh allocations to come
        self.spec_proposed = 0         # draft tokens proposed for this seq
        self.spec_accepted = 0         # proposals the target agreed with
        # multi-tenant / sampled decode
        self.sampling = None           # SamplingParams or None (greedy)
        self.adapter = None            # adapter name or None (base model)
        self.adapter_slot = 0          # slot 0 = reserved no-adapter lane
        self.adapter_sig = (0, 0)      # (slot, generation) cache signature
        self.sample_base = 0           # committed tokens before this run
        self.out_tokens = []           # every committed token (incl. held)
        self.held = []                 # committed, not yet streamed (stop
        #                                hold-back: a possible stop prefix)
        # perf_counter stamps of the sequence span's decomposition
        self.t_submit = time.perf_counter()
        self.t_admit = None            # admission (_begin_sequence)
        self.t_first = None            # first token committed
        self.round_admit = None        # scheduler round it was admitted in
        self.chunks = 0                # prefill dispatches it took


class _Attempt:
    """One submission of a step closure to the step pool. Under the
    engine's lock: `claimed` once its worker has taken the pool for the
    compiled call (`_consume`), `cancelled` once the hang detection has
    given up an attempt that had not: its worker, should it wake, then
    takes no pool. A claimed attempt cannot be given up, it holds the only
    copy of the cache: `done` and what the closure returned or raised are
    how a late one still hands its step in."""
    __slots__ = ("cancelled", "claimed", "done", "result", "error")

    def __init__(self):
        self.cancelled = self.claimed = False
        self.done = threading.Event()
        self.result = self.error = None


class _AttemptRetired(RuntimeError):
    """Raised on a retired step-pool worker in place of its dispatch."""


class _PoolRebuilt(Exception):
    """A dispatch failed after it consumed the pool: the cache was built
    anew and every resident sequence is back in the waiting queue. Ends the
    scheduler's round; nothing of the round's own state is valid."""


#: registry collector keys need a distinct name per engine instance
_ENGINE_SEQ = itertools.count()


class DecodeEngine:
    """Iteration-level (continuous-batching) greedy decode engine over a
    KV-cached causal LM (`decode_step` + `init_block_pool`). See the
    module docstring for semantics and docs/llm_serving.md for the full
    contract and knobs."""

    def __init__(self, model, *, max_length, block_size=16, num_blocks=None,
                 decode_buckets=(1, 2, 4, 8), prefill_buckets=None,
                 quant=None, max_waiting=64, default_timeout=None,
                 step_timeout=30.0, step_retries=1, eos_token_id=None,
                 pad_token_id=0, compile_cache=None, fault_hook=None,
                 hang_grace=0.1, supervise_interval=0.02, metrics=None,
                 mesh=None, sharding_rules=None, clock=time.monotonic,
                 prefix_cache=None, prefix_cache_blocks=None,
                 prefill_chunk=None, draft_model=None, speculate_k=0,
                 draft_num_blocks=None, adapters=None, block_diffusion=None):
        from ...distributed.functional import functionalize
        from ...core.tensor import Tensor

        if max_length < 2:
            raise ValueError("max_length must be >= 2 (prompt + 1 token)")
        bs = sorted({int(b) for b in decode_buckets})
        if not bs or bs[0] < 1:
            raise ValueError(f"decode_buckets must be positive ints, "
                             f"got {decode_buckets}")
        self.model = model
        model.eval()   # greedy decode; dropout under trace is a bug
        self.max_length = int(max_length)
        self.block_size = int(block_size)
        self.decode_buckets = tuple(bs)
        self.max_active = self.decode_buckets[-1]
        self.eos_token_id = eos_token_id
        self.pad_token_id = int(pad_token_id)
        self.default_timeout = default_timeout
        self.step_timeout = step_timeout
        self._step_retries = int(step_retries)
        self._cache = compile_cache
        self._fault_hook = fault_hook
        self._clock = clock
        self._vocab = getattr(getattr(model, "cfg", None), "vocab_size",
                              None)
        self._bd = self._check_block_diffusion(
            block_diffusion, model, quant=quant, mesh=mesh,
            adapters=adapters, draft_model=draft_model,
            speculate_k=speculate_k)
        # layers whose cache is a recurrent state (0: none, the programs
        # and their keys as they were)
        self._recurrent = self._check_recurrent(
            model, quant=quant, mesh=mesh, adapters=adapters,
            draft_model=draft_model, speculate_k=speculate_k,
            prefix_cache=prefix_cache, block_diffusion=block_diffusion)
        if prefix_cache is None:
            prefix_cache = not self._recurrent
        # layers whose cache row is one compressed latent a token, and
        # layers of sparse experts off the block-diffusion path (their
        # counts ride back with the step's tokens); 0: none
        self._latent = getattr(model, "latent_layers", lambda: 0)()
        self._moe_layers = 0 if self._bd is not None else \
            getattr(model, "expert_layers", lambda: 0)()

        if prefill_buckets is None:
            p, buckets = min(8, self.max_length - 1), []
            while p < self.max_length - 1:
                buckets.append(p)
                p *= 2
            buckets.append(self.max_length - 1)
            prefill_buckets = buckets
        self.prefill_buckets = tuple(sorted({int(p) for p in
                                             prefill_buckets}))
        self.max_prompt = min(self.prefill_buckets[-1], self.max_length - 1)

        # chunked prefill (Sarathi-Serve): prompts longer than the chunk
        # are prefilled one block-aligned chunk per scheduler round, so a
        # long prompt never stalls the running decode batch for a full
        # monolithic prefill. The chunk must BE a prefill bucket (chunk
        # dispatches reuse the bucket executables — zero new signatures
        # after warmup) and a multiple of block_size (chunk boundaries
        # are block-table boundaries, which is also what makes
        # chunk-boundary prefix-cache entries exact).
        self._prefix_on = bool(prefix_cache)
        chunk_candidates = [b for b in self.prefill_buckets
                            if b % self.block_size == 0]
        if prefill_chunk is None:
            # auto: the largest aligned bucket a prompt can span at least
            # twice — chunking only matters when prompts outgrow it
            fits = [b for b in chunk_candidates if 2 * b <= self.max_prompt]
            self._chunk = fits[-1] if fits else 0
        elif not prefill_chunk:
            self._chunk = 0
        else:
            c = int(prefill_chunk)
            if c not in chunk_candidates:
                raise ValueError(
                    f"prefill_chunk {c} must be one of the prefill "
                    f"buckets {self.prefill_buckets} and a multiple of "
                    f"block_size {self.block_size}")
            self._chunk = c
        if self._chunk:
            # a chunked prompt never needs a bucket of its own length
            self.max_prompt = self.max_length - 1

        # paged KV pool — the model owns the geometry (cache-entry order,
        # dtypes, quant layout precedence); default capacity fits a full
        # bucket of worst-case-length sequences (+1 copy-on-write block
        # per slot when prefix sharing is on: a sequence whose shared
        # prompt tail ends mid-block COW-copies that one block)
        nb_per_seq = max(1, math.ceil(self.max_length / self.block_size))
        self._nb = nb_per_seq
        if num_blocks is None:
            num_blocks = RESERVED_BLOCKS + self.max_active * (
                nb_per_seq + (1 if self._prefix_on else 0))
        self.pool = model.init_block_pool(
            num_blocks, self.block_size, quant=quant, name="target",
            # one state slot a resident sequence
            **({"num_slots": self.max_active} if self._recurrent else {}))
        # one token's rows over the latent-attention layers
        self._latent_row_bytes = sum(
            t.shape[-1] * t.dtype.itemsize
            for kind, layer in zip(model.cfg.layer_kinds(),
                                   self.pool.tensors)
            if kind == "latent_attention" for t in layer) \
            if self._latent else 0
        if self._bd is not None and (
                self.block_size % self._bd["block_length"]
                or (self._chunk and self._chunk % self._bd["block_length"])):
            raise ValueError(
                f"block_diffusion block_length {self._bd['block_length']} "
                f"must divide block_size {self.block_size}: a committed "
                f"block's rows lie in one pool block")

        # speculative decoding: a draft model proposes speculate_k tokens
        # per round from ITS OWN paged pool (same geometry: max_length /
        # block_size shared, layer/head shapes the draft model's own);
        # the target verifies them in one bucketed dispatch. Off unless
        # both a draft model and speculate_k >= 1 are given —
        # speculate_k=0 is the plain-greedy reference mode the
        # bit-identity gate compares against.
        self._k = int(speculate_k)
        if self._k < 0:
            raise ValueError(f"speculate_k must be >= 0, got {speculate_k}")
        self._spec_on = draft_model is not None and self._k > 0
        self.draft_model = draft_model if self._spec_on else None
        self.draft_pool = None
        if self._spec_on:
            if draft_model is model and mesh is not None:
                raise ValueError(
                    "draft_model must be a distinct model instance when "
                    "a mesh is set: a self-draft shares the target's "
                    "parameter holders, so replicating the draft would "
                    "clobber the target's sharded placement (and a "
                    "self-draft buys no speedup anyway — use a smaller "
                    "draft, or drop the mesh)")
            draft_model.eval()
            dvocab = getattr(getattr(draft_model, "cfg", None),
                             "vocab_size", None)
            if (self._vocab is not None and dvocab is not None
                    and dvocab != self._vocab):
                raise ValueError(
                    f"draft model vocab {dvocab} != target vocab "
                    f"{self._vocab} — proposals would be meaningless")
            if draft_num_blocks is None:
                draft_num_blocks = RESERVED_BLOCKS \
                    + self.max_active * nb_per_seq
            self.draft_pool = draft_model.init_block_pool(
                draft_num_blocks, self.block_size, quant=quant,
                name="draft")
            # draft catch-up chunks at block-aligned starts; a span
            # beyond the largest prefill bucket needs an aligned bucket
            # to chunk with — reject the doomed configuration here, not
            # one user request at a time mid-generation
            if not any(b % self.block_size == 0
                       for b in self.prefill_buckets) \
                    and self.prefill_buckets[-1] < self.max_length - 1:
                raise ValueError(
                    f"speculative draft catch-up needs a prefill bucket "
                    f"that is a multiple of block_size "
                    f"{self.block_size} (got {self.prefill_buckets}) — "
                    f"or a largest bucket spanning max_length - 1 so "
                    f"catch-up never has to chunk")

            def wrapped_draft(tokens, cache_vals, pos):
                cts = [tuple(Tensor(a) for a in entry)
                       for entry in cache_vals]
                logits, new_caches = draft_model.decode_step(
                    Tensor(tokens), cts, Tensor(pos))
                return (logits._value,
                        [tuple(t._value for t in nc) for nc in new_caches])

            self._d_apply, self._d_params, self._d_buffers = functionalize(
                draft_model, method=wrapped_draft)

        # prefix->block-table cache (scheduler-thread owned; counters and
        # structure reads ride _cv): entries pin their blocks with
        # _CACHE_OWNER references and are LRU-evicted under admission
        # pressure or the block cap
        self._prefix_cache = {}        # key -> entry dict
        self._lru = itertools.count()
        if prefix_cache_blocks is None:
            prefix_cache_blocks = max(
                0, (self.pool.num_blocks - RESERVED_BLOCKS) // 2)
        self._prefix_cap = int(prefix_cache_blocks)
        # prefill dispatches can pad past max_length (a chunk's bucket
        # tail): extend the PREFILL-side dense view with extra padding
        # rows so the model's in-graph dynamic_update_slice never clamps
        # — the tail rows scatter into reserved block 0 (garbage sink)
        self._prefill_tail = math.ceil(self.prefill_buckets[-1]
                                       / self.block_size)

        # multi-tenant LoRA serving (S-LoRA/Punica): an AdapterPool over
        # THIS model adds the per-sequence gathered adapter delta through
        # layer post-hooks; the engine threads the slot stacks + per-
        # sequence slot ids through every target dispatch as VALUES, so
        # any tenant mix shares the one compiled executable per bucket
        self._adapters = adapters
        if adapters is not None:
            from .adapter_pool import AdapterPool

            if not isinstance(adapters, AdapterPool):
                raise ValueError(
                    f"adapters must be an AdapterPool, got "
                    f"{type(adapters).__name__}")

        # functional decode step (the generation.py idiom: swap values
        # into the live layers, trace the python forward once). `ats`
        # (adapter stacks) / `aid` (slot ids) enter through the traced
        # adapter context so the pool's post-hooks see them; an empty
        # stacks dict (no adapter pool, or the spec verify path) traces
        # the bare base model — static emptiness, never a retrace.
        def wrapped(tokens, cache_vals, pos, ats, aid, valid=None):
            from .adapter_pool import adapter_context

            cts = [tuple(Tensor(a) for a in entry) for entry in cache_vals]
            if valid is not None:
                # a prompt chunk of a model with recurrent layers or sparse
                # experts: how many of the bucket's positions are real
                logits, new_caches = model.decode_step(
                    Tensor(tokens), cts, Tensor(pos), Tensor(valid))
            elif ats:
                with adapter_context(ats, aid):
                    logits, new_caches = model.decode_step(
                        Tensor(tokens), cts, Tensor(pos))
            else:
                logits, new_caches = model.decode_step(Tensor(tokens), cts,
                                                       Tensor(pos))
            return (logits._value,
                    [tuple(t._value for t in nc) for nc in new_caches])

        self._apply, self._params, self._buffers = functionalize(
            model, method=wrapped)
        if self._bd is not None:
            self._bd_apply = tuple(
                functionalize(model, method=m)[0]
                for m in self._make_bd_forward(model))

        # tensor-parallel placement (paddle_tpu.sharding): weights shard
        # per their logical-axis annotations / the name-pattern rules,
        # paged KV blocks shard along their rows' kv heads, and every step
        # executable compiles partitioned over the mesh (docs/sharding.md)
        self.mesh = mesh
        self._sharding_rules = sharding_rules
        self._param_sh = None
        self._buf_sh = None
        if mesh is not None:
            import jax
            from ... import sharding as _shardlib
            from ...distributed.sharding_spec import (
                DEFAULT_TP_RULES, spec_for_param)

            self._param_sh = {}
            for n, p in self._params.items():
                spec = spec_for_param(n, p, DEFAULT_TP_RULES, mesh=mesh,
                                      axis_rules=sharding_rules)
                sh = _shardlib.named_sharding(mesh, spec)
                p._value = jax.device_put(p._value, sh)
                self._param_sh[n] = sh
            self._buf_sh = {}
            for n, b in self._buffers.items():
                sh = _shardlib.replicated(mesh, b.ndim)
                b._value = jax.device_put(b._value, sh)
                self._buf_sh[n] = sh
            self.pool.shard_(mesh, rules=sharding_rules)
            if self._spec_on:
                # the draft is small by construction: replicate it (and
                # its pool) instead of sharding — every chip proposes the
                # same K tokens, the TP win stays on the target verify
                for holders in (self._d_params, self._d_buffers):
                    for n, h in holders.items():
                        h._value = jax.device_put(
                            h._value, _shardlib.replicated(mesh, h.ndim))
                self._place_draft_pool()

        self._fingerprint = self._make_fingerprint()
        self._draft_fingerprint = self._make_draft_fingerprint() \
            if self._spec_on else None

        self._decode_fns = {}     # bucket -> compiled step
        self._bd_fns = {}         # bucket -> compiled block-diffusion step
        self._prefill_fns = {}    # prompt bucket -> compiled prefill
        self._verify_fns = {}     # bucket -> compiled K+1-position verify
        self._propose_fns = {}    # bucket -> compiled K-step draft propose
        self._draft_prefill_fns = {}   # prompt bucket -> draft catch-up
        self._pool_fns = {}       # "cow": the compiled block copy (COW),
        #                           "zero": the state-slot zeroing
        self._compiled = 0
        self._disk_loaded = 0

        # supervised step executor: ONE slot (steps are inherently
        # serialized — each consumes the previous commit), supervised by
        # the serving runtime's existing hang detection
        self._steps = ServingPool(
            predictor=_NullPredictor(), size=1, max_queue_depth=4,
            default_timeout=None,
            breaker_threshold=max(3, self._step_retries + 2),
            breaker_reset_timeout=0.25,
            retry=RetryPolicy(max_retries=2, base_delay=0.01,
                              max_delay=0.05),
            hang_grace=hang_grace, supervise_interval=supervise_interval,
            metrics=False,  # an internal executor, not a serving surface:
            clock=clock)    # the engine publishes its OWN collector below

        self._lock = _locks.new_lock("decode.engine")
        self._cv = _locks.new_condition("decode.engine", lock=self._lock)
        self._waiting = []            # admission queue (guarded by _cv)
        self._prefill_q = []          # admitted, prompt not fully cached
        self._active = []             # scheduler-owned; mutations under _cv
        self.max_waiting = int(max_waiting)
        self._ids = 0
        self._closed = False
        self._stopping = False
        self._shutdown_called = False
        self._drained = False

        # counters (guarded by _cv's lock)
        self._admitted = 0
        self._completed = 0
        self._failed = 0
        self._timed_out = 0
        self._cancelled = 0
        self._shed = 0
        self._resumed = 0         # resume-from-committed admissions
        self._steps_run = 0
        self._prefills = 0
        self._prefill_chunks = 0
        self._tokens_out = 0
        self._wedged_steps = 0
        self._isolations = 0
        self._donated_dispatches = 0   # compiled calls that took a pool
        self._pool_rebuilds = 0        # fault contract (c)
        self._step_slots = 0
        self._step_active = 0
        self._peak_resident = 0
        self._prefix_hits = 0
        self._prefix_full_hits = 0
        self._prefix_misses = 0
        self._prefix_tokens_reused = 0
        self._prefix_evictions = 0
        self._cow_copies = 0
        self._sampled = 0         # admissions with sampling params
        self._stop_hits = 0       # sequences completed by a stop sequence
        # speculative decoding counters (guarded by _lock like the other
        # dispatch-side counters)
        self._spec_rounds = 0
        self._spec_proposed = 0
        self._spec_accepted = 0
        self._spec_rejected = 0
        self._spec_bonus = 0
        self._spec_committed = 0
        self._spec_verify_dispatches = 0
        self._spec_draft_dispatches = 0
        self._spec_catchup_chunks = 0
        self._spec_fallbacks = 0
        # block diffusion (a dispatch forwards a block a sequence; a
        # forward is one sequence's share of a dispatch) and the expert
        # layers' counts read back with its tokens
        self._bd_forwards = 0
        self._bd_commit_forwards = 0
        self._bd_tokens_fixed = 0
        self._bd_blocks_committed = 0
        self._bd_head_dispatches = 0   # dispatches with a denoising pass
        self._bd_context_tokens = 0    # keys the forwards attended to
        self._moe_choices = 0          # experts chosen, held here or not
        self._moe_tokens = None        # [layers, experts] positions routed
        self._moe_distinct = 0         # experts touched, summed over
        #                                layers and dispatches
        self._moe_chunk_distinct = 0   # ... the prompt chunks' part of it
        self._moe_reads = 0            # experts the schedule read, same sum
        self._moe_load_sum = 0.0       # fullest expert over the mean, summed
        self._moe_load_n = 0           # ... over this many (layer, dispatch)
        # recurrent layers: positions that went through the chunked form
        # (prompt chunks) and through the one-step form (decode)
        self._lin_chunk_tokens = 0
        self._lin_step_tokens = 0
        # scheduler rounds: written by the scheduler thread alone
        # (_slow_rounds under _lock: stats() reads it)
        self._round_no = 0
        self._slow_rounds = 0
        self._round_times = collections.deque(maxlen=_SLOW_ROUND_HISTORY)
        self._last_step = None    # (bucket, member ids) of the round's step

        # telemetry (paddle_tpu.obs): TTFT observed at first-token
        # delivery plus stats() as a registry collector. TWO histograms
        # on purpose: a PRIVATE one backing stats()["ttft"] (per-engine
        # semantics — two engines on one registry must not read each
        # other's TTFT) and the registry's shared process-level family;
        # with metrics=False only the private one exists.
        from ...obs.metrics import Histogram, registry as _obs_registry

        self.name = f"engine{next(_ENGINE_SEQ)}"
        self._h_ttft = Histogram(
            "decode.ttft_seconds",
            help="time to first token: admission -> first delivery")
        if metrics is False:
            self._metrics = None
            self._h_ttft_shared = None
            self._h_queue_wait = None
        else:
            self._metrics = metrics if metrics is not None \
                else _obs_registry()
            self._h_ttft_shared = self._metrics.histogram(
                "decode.ttft_seconds",
                help="time to first token: admission -> first delivery")
            self._h_queue_wait = self._metrics.histogram(
                "decode.queue_wait_seconds",
                help="submit -> admission into the resident batch")

        self._thread = threading.Thread(target=self._loop,
                                        name="DecodeEngine-scheduler",
                                        daemon=True)
        self._thread.start()
        if self._metrics is not None:
            # last: a concurrent scrape must only see a fully-built engine
            self._metrics.register_collector(
                f"decode.{self.name}", self.stats)

    def _place_draft_pool(self):
        """The draft's pool replicated over the mesh, like the draft."""
        if self.mesh is None:
            return
        import jax
        from ... import sharding as _shardlib

        self.draft_pool.tensors = [
            tuple(jax.device_put(
                t, _shardlib.replicated(self.mesh, t.ndim)) for t in layer)
            for layer in self.draft_pool.tensors]

    # -- identity ----------------------------------------------------------
    def _make_fingerprint(self):
        """Model/program identity for the persistent compile cache:
        structure and shapes, never weight VALUES (weights are runtime
        arguments of the step executable)."""
        h = hashlib.sha256()
        h.update(type(self.model).__name__.encode())
        for n in sorted(self._params):
            p = self._params[n]
            h.update(f"{n}:{tuple(p.shape)}:{p.dtype}".encode())
        for n in sorted(self._buffers):
            b = self._buffers[n]
            h.update(f"{n}:{tuple(b.shape)}:{b.dtype}".encode())
        h.update(f"paged-scan-mt-v4:{self.pool.quant}:"
                 f"{self.block_size}:{self._nb}:{self._prefill_tail}:"
                 f"{self.max_length}".encode())
        if self._adapters is not None:
            # the adapter stacks are step-executable INPUTS: their
            # geometry (rank/slots/target layers) is part of the
            # program's identity exactly like the weight avals above
            h.update(f"adapters:{self._adapters.geometry()}".encode())
        # what shapes the program and is no parameter's shape (a block
        # mask, a routing rule): named by the model, "" for the models
        # that have none, whose keys stay as they were
        sig = getattr(self.model, "decode_signature", lambda: "")()
        if sig or self._bd is not None:
            h.update(f"model:{sig}:bd:{self._bd}".encode())
        if self.mesh is not None:
            # a TP engine compiles different programs — its disk-cache
            # entries must never collide with the single-device ones
            h.update(f"mesh:{sorted(dict(self.mesh.shape).items())}".encode())
        return h.hexdigest()

    def _make_draft_fingerprint(self):
        """Identity of the DRAFT model's compiled programs (propose +
        catch-up prefill): draft structure/shapes, never values — kept
        separate from the target fingerprint so the target's decode /
        prefill / verify executables are shared with a draft-less engine
        over the same target model."""
        h = hashlib.sha256()
        h.update(type(self.draft_model).__name__.encode())
        for n in sorted(self._d_params):
            p = self._d_params[n]
            h.update(f"{n}:{tuple(p.shape)}:{p.dtype}".encode())
        for n in sorted(self._d_buffers):
            b = self._d_buffers[n]
            h.update(f"{n}:{tuple(b.shape)}:{b.dtype}".encode())
        h.update(f"spec-draft-v3:{self.draft_pool.quant}:"
                 f"{self.block_size}:{self._nb}:{self._prefill_tail}"
                 .encode())
        if self.mesh is not None:
            h.update(f"mesh:{sorted(dict(self.mesh.shape).items())}"
                     .encode())
        return h.hexdigest()

    # -- admission ---------------------------------------------------------
    def submit(self, prompt_ids, max_new_tokens, timeout=None, *,
               resume_committed=None, sampling=None, adapter=None):
        """Admit one generation request; returns its `SequenceStream`.

        Validation errors (malformed *request*: bad dtype/rank, empty or
        over-long prompt, out-of-vocab ids) raise `ValueError`
        synchronously. Admission shedding mirrors `ServingPool`: a full
        waiting queue raises `Overloaded`, a closed engine `PoolClosed`,
        a dead-on-arrival deadline `DeadlineExceeded`. The deadline
        (`timeout` seconds, None -> `default_timeout`, both None ->
        unbounded) covers queue wait AND the whole generation.

        `sampling` (a `SamplingParams`, or its `to_dict()` wire form)
        turns on per-request in-graph sampling; `None` is the greedy
        path, bit-identical at every bucket to the engine before
        sampling existed. `adapter` names a LoRA adapter in the engine's
        `AdapterPool`; an unknown name raises the typed
        `AdapterNotLoaded` (a deterministic request error — the serving
        tier fails fast, no failover, no health penalty). Both ride the
        batch as per-sequence VALUES, so arbitrary mixes share the
        compiled executables — zero post-warmup retraces.

        `resume_committed` is the mid-stream failover admission path
        (docs/serving.md): tokens already committed to the client by a
        prior attempt on another replica become a prompt extension, so
        this sequence decodes the CONTINUATION — greedy decode over the
        absolute-chunk-boundary prefill makes the resumed output
        bit-identical to the uninterrupted run, and the prefix cache
        makes the re-prefill cheap. Sampled sequences resume
        bit-identically too: the per-token RNG key is a counter folded
        into the request seed, and the counter restarts at the committed
        length. The stream yields only the new tokens (the caller owns
        stitching)."""
        from ..sampling import SamplingParams

        if sampling is not None and not isinstance(sampling,
                                                   SamplingParams):
            sampling = SamplingParams.from_dict(dict(sampling))
        ids = np.asarray(prompt_ids)
        committed = 0
        if resume_committed is not None and len(resume_committed):
            ext = np.asarray(resume_committed)
            if ids.ndim == 2 and ids.shape[0] == 1:
                ids = ids[0]
            if ext.ndim != 1 or not np.issubdtype(ext.dtype, np.integer):
                raise ValueError(
                    f"resume_committed must be a 1-D integer id array, "
                    f"got shape {ext.shape} dtype {ext.dtype}")
            committed = int(ext.shape[0])
            ids = np.concatenate([ids.astype(np.int64),
                                  ext.astype(np.int64)])
        if ids.ndim == 2 and ids.shape[0] == 1:
            ids = ids[0]
        if ids.ndim != 1 or not np.issubdtype(ids.dtype, np.integer):
            raise ValueError(
                f"prompt must be a 1-D integer id array, got shape "
                f"{ids.shape} dtype {ids.dtype}")
        if not 1 <= ids.shape[0] <= self.max_prompt:
            raise ValueError(
                f"prompt length {ids.shape[0]} outside [1, "
                f"{self.max_prompt}] (largest prefill bucket / "
                f"max_length - 1)")
        if ids.size and (int(ids.min()) < 0 or (
                self._vocab is not None and int(ids.max()) >= self._vocab)):
            raise ValueError(
                f"prompt ids must be in [0, {self._vocab}) — got range "
                f"[{int(ids.min())}, {int(ids.max())}] (poisoned feed?)")
        max_new = int(max_new_tokens)
        if max_new < 1:
            raise ValueError(f"max_new_tokens must be >= 1, got {max_new}")
        if self._bd is not None and (committed or not (
                sampling is None or sampling.is_greedy())):
            raise ValueError(
                "block diffusion decodes greedily from the prompt: "
                "sampling params and resume_committed are refused (a "
                "resumed prompt would move the blocks' boundaries)")
        rows = self._cache_rows(ids.shape[0], max_new)
        if rows > self.max_length:
            raise ValueError(
                f"prompt ({ids.shape[0]}) + max_new_tokens ({max_new}) "
                f"exceeds max_length {self.max_length}"
                + ("" if self._bd is None else
                   f" ({rows} cache rows in whole blocks)"))
        worst = self.pool.blocks_for(rows) + (
            1 if self._prefix_on and self._prefill_len(ids.shape[0])
            % self.block_size else 0)
        if worst > self.pool.num_blocks - RESERVED_BLOCKS:
            raise ValueError(
                f"request needs {worst} worst-case blocks but the pool "
                f"holds only {self.pool.num_blocks - RESERVED_BLOCKS} "
                f"allocatable — it could never be admitted")
        if self._spec_on:
            dworst = self._draft_worst(ids.shape[0], max_new)
            if dworst > self.draft_pool.num_blocks - RESERVED_BLOCKS:
                raise ValueError(
                    f"request needs {dworst} worst-case DRAFT blocks but "
                    f"the draft pool holds only "
                    f"{self.draft_pool.num_blocks - RESERVED_BLOCKS} "
                    f"allocatable — it could never be admitted")

        eff = self.default_timeout if timeout is None else timeout
        dl = Deadline(eff, clock=self._clock)
        with self._cv:
            if self._closed:
                self._shed += 1
                raise PoolClosed(
                    "decode engine is shut down — admission refused")
            if dl.expired():
                self._shed += 1
                raise DeadlineExceeded(
                    "dead on arrival: deadline expired before admission")
            if len(self._waiting) >= self.max_waiting:
                self._shed += 1
                raise Overloaded(
                    f"decode waiting queue full ({self.max_waiting} deep) "
                    f"— request shed; retry with backoff")
            self._ids += 1
            seq = _Seq(self._ids, ids.astype(np.int32), max_new, dl)
            seq.prefill_ids = seq.prompt[:self._prefill_len(len(seq.prompt))]
            seq.sampling = sampling
            seq.sample_base = committed
            if adapter is not None:
                if self._adapters is None:
                    raise AdapterNotLoaded(
                        f"adapter {adapter!r} requested but this engine "
                        f"has no adapter pool (pass adapters= to "
                        f"DecodeEngine)")
                # pin the adapter's slot for this sequence's lifetime: a
                # hot-reload of the same NAME lands in a fresh slot and
                # this sequence keeps decoding under the weights it was
                # admitted with (generation purity)
                slot, gen = self._adapters.acquire(adapter, owner=seq.id)
                seq.adapter = adapter
                seq.adapter_slot = slot
                seq.adapter_sig = (slot, gen)
            seq.submitted_at = self._clock()
            # per-sequence root span: lives across scheduler rounds
            # (detached from any thread stack), closed by _finish with
            # the sequence's terminal status; child of the submitting
            # caller's trace when one is active
            if _otrace.enabled():
                seq.span = _otrace.open_span(
                    "decode.sequence",
                    attrs={"engine": self.name, "seq": seq.id,
                           "prompt_len": int(ids.shape[0]),
                           "max_new": max_new,
                           **({"resumed_from": committed}
                              if committed else {}),
                           **({"adapter": adapter} if adapter else {}),
                           **({"sampled": True}
                              if sampling is not None else {})})
            seq.stream._cancel = lambda s=seq: self._request_cancel(s)
            self._waiting.append(seq)
            self._admitted += 1
            if sampling is not None:
                self._sampled += 1
            if committed:
                self._resumed += 1
            self._cv.notify()
        return seq.stream

    def generate(self, prompt_ids, max_new_tokens, timeout=None, *,
                 sampling=None, adapter=None):
        """Synchronous convenience: submit + drain; returns the generated
        token list or raises the typed serving error."""
        return self.submit(prompt_ids, max_new_tokens, timeout=timeout,
                           sampling=sampling, adapter=adapter).result()

    def _request_cancel(self, seq):
        with self._cv:
            seq.cancelled = True
            self._cv.notify()

    # -- block diffusion: configuration and geometry -----------------------
    @staticmethod
    def _check_block_diffusion(opt, model, *, quant, mesh, adapters,
                               draft_model, speculate_k):
        """The `block_diffusion` option as a dict of ints, or None. What
        has no test together with it is refused here, at construction."""
        if not opt:
            return None
        try:
            bd = {k: int(opt[k]) for k in ("block_length",
                                           "denoising_steps",
                                           "mask_token_id")}
        except (KeyError, TypeError, ValueError) as e:
            raise ValueError(
                f"block_diffusion needs integer block_length, "
                f"denoising_steps and mask_token_id, got {opt!r}") from e
        bl, steps = bd["block_length"], bd["denoising_steps"]
        if bl < 1 or steps < 1 or bl % steps:
            raise ValueError(
                f"block_diffusion: denoising_steps ({steps}) must divide "
                f"block_length ({bl}): a pass fixes block_length / "
                f"denoising_steps positions")
        cfg = getattr(model, "cfg", None)
        want = getattr(cfg, "block_attention", 0) or 1
        if want != bl:
            raise ValueError(
                f"block_diffusion block_length {bl} is not the model's "
                f"attention block ({want}): the mask the model runs under "
                f"is its configuration's `block_attention`")
        vocab = getattr(cfg, "vocab_size", None)
        if vocab is not None and not 0 <= bd["mask_token_id"] < vocab:
            raise ValueError(
                f"block_diffusion mask_token_id {bd['mask_token_id']} "
                f"outside the vocabulary [0, {vocab})")
        if not (hasattr(model, "transformer") and hasattr(model, "_project")):
            raise ValueError(
                "block_diffusion needs a model with `transformer."
                "forward_step` and `_project` (GPTForCausalLM): a commit "
                "pass runs the trunk without the head")
        refused = [n for n, on in (
            ("quant", quant is not None
             or getattr(model, "cache_quant", None) is not None),
            ("mesh", mesh is not None), ("adapters", adapters is not None),
            ("draft_model / speculate_k",
             draft_model is not None or speculate_k)) if on]
        if refused:
            raise ValueError(
                f"block_diffusion does not compose with "
                f"{', '.join(refused)} yet (untested together: refused "
                f"rather than served unproven)")
        return bd

    @staticmethod
    def _check_recurrent(model, *, quant, mesh, adapters, draft_model,
                         speculate_k, prefix_cache, block_diffusion):
        """How many of the model's layers keep a recurrent state. With
        any, what rests on a cache made of rows is refused here: a state
        cannot be matched by prefix, shared by reference, copied a block
        at a time or rolled back a position."""
        def count(m):
            return getattr(m, "recurrent_layers", lambda: 0)()

        n = count(model)
        if draft_model is not None and count(draft_model):
            raise ValueError(
                "draft_model has recurrent layers: speculation rolls a "
                "rejected position back by rewriting its row, and a "
                "recurrent state has no rows")
        if not n:
            return 0
        refused = [name for name, on in (
            ("prefix_cache (and its copy-on-write): a cached prefix is "
             "blocks of rows, and the state after it was not kept",
             bool(prefix_cache)),
            ("draft_model / speculate_k: a rejected position cannot be "
             "rolled back out of a state",
             draft_model is not None or bool(speculate_k)),
            ("block_diffusion: a denoising pass must leave the cache as "
             "it was", bool(block_diffusion)),
            ("quant: the int8 layout is for rows",
             quant is not None
             or getattr(model, "cache_quant", None) is not None),
            ("mesh: state slots have no sharding rule", mesh is not None),
            ("adapters: untested with the delta-rule projections",
             adapters is not None)) if on]
        if refused:
            raise ValueError(
                f"this model keeps a recurrent state in {n} of its layers "
                f"(one slot a sequence, not rows); it does not compose "
                f"with " + "; ".join(refused))
        return n

    def _prefill_len(self, plen):
        """Prompt tokens that prefill puts in the cache: all of them, or
        under block diffusion the whole blocks (the remainder opens the
        first generated block as positions already fixed)."""
        if self._bd is None:
            return plen
        return plen // self._bd["block_length"] * self._bd["block_length"]

    def _cache_rows(self, plen, max_new):
        """Cache rows a request can come to write: prompt + answer, under
        block diffusion rounded up to whole blocks (the last block's tail
        is computed and committed, not delivered)."""
        if self._bd is None:
            return plen + max_new
        bl = self._bd["block_length"]
        return -(-(plen + max_new) // bl) * bl

    # -- compiled programs -------------------------------------------------
    def _avals(self, arrays):
        import jax

        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(np.shape(a), a.dtype), arrays)

    def _weight_avals(self):
        import jax

        pv = {n: jax.ShapeDtypeStruct(tuple(p.shape), p.dtype)
              for n, p in self._params.items()}
        bv = {n: jax.ShapeDtypeStruct(tuple(b.shape), b.dtype)
              for n, b in self._buffers.items()}
        return pv, bv

    def _installed(self, store, key, compiled, source):
        """Keep a program `compile_jit` brought up and count its build
        ("compiled") or persistent-cache load ("disk"): every program
        builder ends here. None where a `cached_only` builder found
        nothing cached."""
        if compiled is not None:
            with self._lock:
                if source == "disk":
                    self._disk_loaded += 1
                else:
                    self._compiled += 1
            store[key] = compiled
        return compiled

    def _step_shardings(self):
        """(pv, bv, pool, scalar) sharding pytrees for the TP step
        executables (mesh set), else None."""
        if self.mesh is None:
            return None
        from ... import sharding as _shardlib

        repl = _shardlib.replicated(self.mesh)
        pool_sh = [tuple(layer) for layer in self.pool.shardings]
        return self._param_sh, self._buf_sh, pool_sh, repl

    def _slot_layer(self, i):
        """Whether layer `i` of the target pool holds a recurrent state
        (one slot a sequence) and not blocks of rows."""
        return bool(self._recurrent) and self.pool.slot_layers[i]

    def _gather(self, pool_ts, table, nb=None, slot=None):
        """Dense per-sequence cache view: every pool tensor gathered
        through the block table into [1, NB*block_size, ...]. Prefill
        passes an EXTENDED table (`nb = _nb + _prefill_tail`, tail rows
        pointing at reserved block 0) so a chunk's bucket padding can
        never clamp the in-graph cache update. A recurrent layer's entry
        is read by `slot`: [1, ...], the whole of it."""
        nb = self._nb if nb is None else nb
        caches = []
        for i, layer in enumerate(pool_ts):
            if self._slot_layer(i):
                caches.append(tuple(t[slot][None] for t in layer))
                continue
            entry = []
            for t in layer:
                g = t[table]                       # [NB, bs, *suffix]
                entry.append(g.reshape((1, nb * self.block_size)
                                       + g.shape[2:]))
            caches.append(tuple(entry))
        return caches

    def _scatter_row(self, pool_ts, new_caches, table, pos):
        """Write the cache row the step produced at `pos` back into the
        pool (one sequence of a scanned step)."""
        block = table[pos // self.block_size]
        off = pos % self.block_size
        return [tuple(t.at[block, off].set(r.astype(t.dtype))
                      for t, r in zip(layer_ts, layer_rows))
                for layer_ts, layer_rows in zip(
                    pool_ts, self._new_rows(new_caches, pos))]

    def _new_rows(self, new_caches, pos):
        """The cache row of every pool tensor that a one-token step wrote
        at `pos` (the only row `decode_step` changed); of a recurrent
        layer, the whole new entry."""
        import jax

        return [tuple(c[0] for c in layer) if self._slot_layer(i) else
                tuple(jax.lax.dynamic_index_in_dim(c, pos, axis=1,
                                                   keepdims=False)[0]
                      for c in layer)
                for i, layer in enumerate(new_caches)]

    def _scatter_new_rows(self, pool_ts, rows, tables, positions,
                          slots=None):
        """Write a bucket's new rows (`_new_rows`, stacked `[B, ...]`)
        into the pool, a tensor by one scatter at `(table[pos // bs],
        pos % bs)`, a recurrent layer's entries at `slots`. Padded slots
        carry table 0, position 0 and slot 0: they all land on one row of
        reserved block 0 and on reserved slot 0, the padding sinks."""
        import jax.numpy as jnp

        blocks = jnp.take_along_axis(
            tables, (positions // self.block_size)[:, None], axis=1)[:, 0]
        offs = positions % self.block_size
        return [tuple(t.at[slots].set(r.astype(t.dtype))
                      for t, r in zip(layer_ts, layer_rows))
                if self._slot_layer(i) else
                tuple(t.at[blocks, offs].set(r.astype(t.dtype))
                      for t, r in zip(layer_ts, layer_rows))
                for i, (layer_ts, layer_rows) in enumerate(
                    zip(pool_ts, rows))]

    def _scatter_new_blocks(self, pool_ts, rows, tables, positions, live):
        """Write a bucket's block rows (`[B, n, ...]` a pool tensor, the
        rows a block forward made at `positions`) into the pool, a tensor
        by one scatter of B windows: a slot's own block where `live` (a
        commit pass), else reserved block 0, the padding sink (a denoising
        pass and a padded slot write no cache). A window lies in one pool
        block: `n` divides `block_size` and a position is a multiple of
        `n`."""
        import jax
        import jax.numpy as jnp

        blocks = jnp.take_along_axis(
            tables, (positions // self.block_size)[:, None], axis=1)[:, 0]
        at = jnp.stack([jnp.where(live != 0, blocks, 0),
                        positions % self.block_size], axis=1)
        out = []
        for layer_ts, layer_rows in zip(pool_ts, rows):
            entry = []
            for t, r in zip(layer_ts, layer_rows):
                dims = jax.lax.ScatterDimensionNumbers(
                    update_window_dims=tuple(range(1, t.ndim)),
                    inserted_window_dims=(0,),
                    scatter_dims_to_operand_dims=(0, 1))
                entry.append(jax.lax.scatter(t, at, r.astype(t.dtype), dims))
            out.append(tuple(entry))
        return out

    @staticmethod
    def _make_bd_forward(model):
        """The two traced halves of a block forward. `trunk`: tokens
        [1, B] at offset `pos` against the gathered cache, returns the
        final hidden states [B, hidden], the caches with the block's rows
        written, and the expert layers' position counts `[layers,
        experts]`. `head`: per position of hidden [..., hidden] the arg-max
        token of its own logits (no shift) and that token's softmax
        probability."""
        import jax
        import jax.numpy as jnp
        from ...core.tensor import Tensor
        from ...models.moe import expert_counts

        def trunk(tokens, cache_vals, pos):
            cts = [tuple(Tensor(a) for a in entry) for entry in cache_vals]
            with expert_counts() as counts:
                hidden, new_caches = model.transformer.forward_step(
                    Tensor(tokens), cts, Tensor(pos))
            counts = jnp.stack(counts) if counts \
                else jnp.zeros((0, 0), jnp.int32)
            return (hidden._value[0],
                    [tuple(t._value for t in nc) for nc in new_caches],
                    counts)

        def head(hidden):
            lg = model._project(Tensor(hidden))._value.astype(jnp.float32)
            top = jnp.max(lg, axis=-1)
            return (jnp.argmax(lg, axis=-1).astype(jnp.int32),
                    jnp.exp(top - jax.nn.logsumexp(lg, axis=-1)))

        return trunk, head

    def _bd_fn(self, bucket, cached_only=False):
        """Block-diffusion step for `bucket` sequences as ONE batched
        forward: the per-sequence block forward (`[1, B]` tokens at the
        sequence's own offset against its own gathered view) traced once
        and batched by `vmap` with the weights and the pool closed over,
        as `_forward_bucket` does for the plain step. Every dense
        projection sees `[bucket, B, hidden]` and reads its weight once a
        dispatch, and the expert layer sees the dispatch's bucket x B
        positions at once (`models/moe.py::apply_experts` folds the batch
        axis into its positions), so an expert crosses HBM at most once a
        layer a dispatch. What it promises is `_forward_bucket`'s contract
        (module docstring), not bit-equality across bucket sizes: a
        sequence's expert sum runs in expert order where the dispatch has
        more assignments than the layer has experts, else in assignment
        order.

        A sequence's phase is DATA (`commit`): the same program denoises
        one sequence and commits another, and only a commit writes cache
        rows, after the forward (a denoising pass's rows sink into
        reserved block 0). The head runs once for the whole bucket and is
        skipped where no sequence of the dispatch denoises. Padded slots
        (`valid` 0) commit nothing and count no expert."""
        fn = self._bd_fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        bl = self._bd["block_length"]
        trunk, head = self._bd_apply

        def step(pv, bv, pool_ts, tokens, positions, tables, commit, valid):
            def one(toks, pos0, table):
                caches = self._gather(pool_ts, table)
                (hidden, new_caches, counts), _ = trunk(
                    pv, bv, toks.reshape(1, bl), caches, pos0)
                rows = [tuple(jax.lax.dynamic_slice_in_dim(
                    c[0], pos0, bl, axis=0) for c in layer)
                    for layer in new_caches]
                return hidden, rows, counts

            hidden, rows, counts = jax.vmap(one)(tokens, positions, tables)
            # one cond for the dispatch, outside the vmap: a cond a
            # sequence would become a select of both branches under it
            best, conf = jax.lax.cond(
                jnp.all((commit != 0) | (valid == 0)),
                lambda h: (jnp.zeros(h.shape[:2], jnp.int32),
                           jnp.zeros(h.shape[:2], jnp.float32)),
                lambda h: head(pv, bv, h)[0], hidden)
            pool_ts = self._scatter_new_blocks(
                pool_ts, rows, tables, positions, commit * valid)
            counts = jnp.sum(counts * valid[:, None, None], axis=0)
            return pool_ts, (best, conf, counts)

        pv, bv = self._weight_avals()
        row = jax.ShapeDtypeStruct((bucket,), jnp.int32)
        avals = (pv, bv, self._avals(self.pool.tensors),
                 jax.ShapeDtypeStruct((bucket, bl), jnp.int32), row,
                 jax.ShapeDtypeStruct((bucket, self._nb), jnp.int32),
                 row, row)
        # its own `extra_key`, as `_decode_fn`'s: a cache filled by the
        # scanned step under (tag, fingerprint, avals) must not serve this
        # program. Bump it whenever this program's text changes
        compiled, source = aot.compile_jit(
            step, avals, cached_only=cached_only,
            fingerprint=self._fingerprint, cache=self._cache,
            tag=f"decode-step-bd-b{bucket}", audit_ctx=self._audit_ctx(pv),
            donate_argnums=(2,), extra_key="batched-forward-v1")
        return self._installed(self._bd_fns, bucket, compiled, source)

    def _adapter_avals(self):
        """Abstract values of the adapter slot stacks riding every
        target dispatch ({} without an adapter pool — static emptiness,
        one signature either way)."""
        return self._adapters.stack_avals() \
            if self._adapters is not None else {}

    def _adapter_stacks(self):
        """Current stack VALUES, fetched per dispatch so a hot-load
        rides the very next step without recompiling anything."""
        return self._adapters.stacks() \
            if self._adapters is not None else {}

    def _forward_bucket(self, pv, bv, ats, pool_ts, tokens, positions,
                        tables, aids, slots=None):
        """ONE forward for a bucket of one-token steps (traced): float32
        logits `[B, vocab]` and the cache rows the step made (`_new_rows`,
        stacked `[B, ...]`), the pool itself untouched; with expert layers
        also their position counts `[B, layers, experts held]`.

        The per-sequence program (the model's one definition of a cached
        step, `decode_step`) is traced once and batched by `vmap` with
        the pool, the weights and the adapter stacks closed over: every
        dense layer sees a `[B, 1, hidden]` activation, so a weight
        crosses HBM once a step and not once a sequence. Attention stays
        per sequence in meaning: each row attends to its own cache rows
        at its own position through its own block table, and the adapter
        delta gathers each row's own slot (slot 0 = the base model)."""
        import jax
        import jax.numpy as jnp

        from ...models.moe import expert_counts

        def one(tok, pos, table, aid, *slot):
            caches = self._gather(pool_ts, table,
                                  slot=slot[0] if slot else None)
            with expert_counts() as counts:
                (logits, new_caches), _ = self._apply(
                    pv, bv, tok.reshape(1, 1), caches, pos, ats, aid)
            out = (logits[0, -1].astype(jnp.float32),
                   self._new_rows(new_caches, pos))
            # a padded slot (block table all 0, as nothing live has) is
            # routed like any token and counted nowhere
            return out + ((jnp.stack(counts) * (table[0] != 0),)
                          if self._moe_layers else ())

        # a model with recurrent layers reads each row's state by its slot
        return jax.vmap(one)(tokens, positions, tables, aids,
                             *(() if slots is None else (slots,)))

    def _decode_fn(self, bucket, cached_only=False):
        fn = self._decode_fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot
        from ..sampling import sample_token, samp_pack_avals

        def step(pv, bv, ats, pool_ts, tokens, positions, tables,
                 aids, hist, samp, slots=None):
            logits, rows, *counts = self._forward_bucket(
                pv, bv, ats, pool_ts, tokens, positions, tables, aids,
                slots)
            # greedy rows (`samp["greedy"] == 1`) select the raw-logits
            # argmax behind a where; sampled rows draw from the counter-
            # keyed per-sequence RNG. Row by row, not under `vmap`: the
            # session's `rbg` keys draw other bits when batched, and a
            # sequence's stream may not depend on its slot or its
            # batchmates (a resumed or isolated sequence redraws it). A
            # mixed-tenant mixed-sampling batch is still this one
            # executable.
            nxt = jax.lax.map(lambda row: sample_token(*row),
                              (logits, samp, hist))
            if counts:
                # the expert layers' counts ride back behind the tokens:
                # one array, one readback (`_take_expert_counts`)
                nxt = jnp.concatenate(
                    [nxt, jnp.sum(counts[0], axis=0).reshape(-1)])
            return self._scatter_new_rows(
                pool_ts, rows, tables, positions, slots), nxt

        pv, bv = self._weight_avals()
        ats_avals = self._adapter_avals()
        samp_avals = samp_pack_avals(bucket)
        avals = (pv, bv, ats_avals, self._avals(self.pool.tensors),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket, self._nb), jnp.int32),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket, self.max_length),
                                      jnp.int32),
                 samp_avals)
        if self._recurrent:
            # the state slots ride last: the other models' programs keep
            # their signature
            avals += (jax.ShapeDtypeStruct((bucket,), jnp.int32),)
        in_sh = out_sh = None
        sh = self._step_shardings()
        if sh is not None:
            pv_sh, bv_sh, pool_sh, repl = sh
            ats_sh = jax.tree_util.tree_map(lambda _: repl, ats_avals)
            samp_sh = jax.tree_util.tree_map(lambda _: repl, samp_avals)
            in_sh = (pv_sh, bv_sh, ats_sh, pool_sh, repl, repl, repl,
                     repl, repl, samp_sh)
            out_sh = (pool_sh, repl)
        # `extra_key` names THIS program in its executables' cache keys:
        # `compile_jit` keys on (tag, fingerprint, avals), not on the
        # program's text, and the fingerprint's own version string also
        # keys the prefill, COW and block-diffusion executables, which
        # did not change when this step became one batched forward.
        # Bump it whenever this program's text changes
        compiled, source = aot.compile_jit(
            step, avals, cached_only=cached_only,
            fingerprint=self._fingerprint, cache=self._cache,
            tag=f"decode-step-b{bucket}", in_shardings=in_sh,
            out_shardings=out_sh, audit_ctx=self._audit_ctx(pv),
            donate_argnums=(3,), extra_key="batched-forward-v2")
        return self._installed(self._decode_fns, bucket, compiled, source)

    def _make_prefill_body(self, pbucket, apply, multiplex=False):
        """The traced chunk-prefill program, shared by the target
        prefill and the draft catch-up prefill (`apply` selects whose
        weights run the forward). The block-wise scatter below is the
        bit-exactness-critical core both chunked prefill and draft
        catch-up rest on — one implementation, two compilers.

        `multiplex=True` (the target) threads the adapter stacks / slot
        id through the forward (the adapter delta changes the PROMPT KV
        too, not just decode) and samples the next token through the
        samp pack — the final chunk of a sampled sequence draws its
        first generated token here. The draft keeps the plain greedy
        signature (speculation is greedy-only)."""
        import jax
        import jax.numpy as jnp

        nb_written = math.ceil(pbucket / self.block_size)
        nb_table = self._nb + self._prefill_tail

        def scatter(pool_ts, new_caches, table, start, slot=None):
            # scatter the written rows block-by-block from the chunk's
            # start block; rows past the real tokens are garbage that
            # decode overwrites position-by-position before it can ever
            # be attended, and rows past the allocated blocks land in
            # reserved block 0 (the padding sink). A recurrent layer hands
            # its state and window to the next chunk through its slot
            sb = start // self.block_size
            out = []
            for i, (layer_ts, layer_new) in enumerate(zip(pool_ts,
                                                          new_caches)):
                if multiplex and self._slot_layer(i):
                    out.append(tuple(t.at[slot].set(c[0].astype(t.dtype))
                                     for t, c in zip(layer_ts, layer_new)))
                    continue
                entry = []
                for t, c in zip(layer_ts, layer_new):
                    new_t = t
                    for j in range(nb_written):
                        lo = j * self.block_size
                        hi = min(pbucket, lo + self.block_size)
                        rows = jax.lax.dynamic_slice_in_dim(
                            c[0], start + lo, hi - lo, axis=0
                        ).astype(t.dtype)
                        new_t = new_t.at[table[sb + j], : hi - lo].set(rows)
                    entry.append(new_t)
                out.append(tuple(entry))
            return out

        if multiplex:
            from ...models.moe import expert_counts
            from ..sampling import sample_token

            def prefill(pv, bv, ats, pool_ts, tokens, start, valid_len,
                        table, aid, hist, samp, slot=None):
                # chunk-aware prefill: tokens [1, pbucket] hold prompt
                # positions [start, start + valid_len); `start` is
                # always block-aligned (0 for a monolithic prefill).
                # Attention over already-written earlier chunks rides
                # the same gathered view. With recurrent layers the model
                # is told `valid_len`: the bucket's padding must leave the
                # state as the last real position left it.
                caches = self._gather(pool_ts, table, nb=nb_table,
                                      slot=slot)
                with expert_counts() as counts:
                    (logits, new_caches), _ = apply(
                        pv, bv, tokens, caches, start, ats, aid,
                        *((valid_len,) if self._recurrent
                          or self._moe_layers else ()))
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], valid_len - 1, axis=0, keepdims=False)
                nxt = sample_token(last.astype(jnp.float32), samp, hist)
                if self._moe_layers:
                    nxt = jnp.concatenate(
                        [nxt[None], jnp.stack(counts).reshape(-1)])
                return scatter(pool_ts, new_caches, table, start,
                               slot), nxt
        else:
            def prefill(pv, bv, pool_ts, tokens, start, valid_len, table):
                caches = self._gather(pool_ts, table, nb=nb_table)
                (logits, new_caches), _ = apply(pv, bv, tokens, caches,
                                                start)
                last = jax.lax.dynamic_index_in_dim(
                    logits[0], valid_len - 1, axis=0, keepdims=False)
                nxt = jnp.argmax(last.astype(jnp.float32),
                                 -1).astype(jnp.int32)
                return scatter(pool_ts, new_caches, table, start), nxt

        return prefill

    def _prefill_fn(self, pbucket, cached_only=False):
        fn = self._prefill_fns.get(pbucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        from ..sampling import samp_pack_avals

        nb_table = self._nb + self._prefill_tail
        prefill = self._make_prefill_body(pbucket, self._apply,
                                          multiplex=True)
        pv, bv = self._weight_avals()
        ats_avals = self._adapter_avals()
        samp_avals = samp_pack_avals(None)   # one sequence: scalar rows
        avals = (pv, bv, ats_avals, self._avals(self.pool.tensors),
                 jax.ShapeDtypeStruct((1, pbucket), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((nb_table,), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((self.max_length,), jnp.int32),
                 samp_avals)
        if self._recurrent:
            avals += (jax.ShapeDtypeStruct((), jnp.int32),)
        in_sh = out_sh = None
        sh = self._step_shardings()
        if sh is not None:
            pv_sh, bv_sh, pool_sh, repl = sh
            ats_sh = jax.tree_util.tree_map(lambda _: repl, ats_avals)
            samp_sh = jax.tree_util.tree_map(lambda _: repl, samp_avals)
            in_sh = (pv_sh, bv_sh, ats_sh, pool_sh,
                     self._prefill_tokens_sharding(pbucket, repl),
                     repl, repl, repl, repl, repl, samp_sh)
            out_sh = (pool_sh, repl)
        compiled, source = aot.compile_jit(
            prefill, avals, cached_only=cached_only,
            fingerprint=self._fingerprint,
            cache=self._cache, tag=f"decode-prefill-p{pbucket}",
            in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(3,), audit_ctx=self._audit_ctx(pv))
        return self._installed(self._prefill_fns, pbucket, compiled, source)

    def _prefill_tokens_sharding(self, pbucket, repl):
        """Sharding for the prefill token buffer [1, pbucket].

        On a mesh with a `cp` axis, prefill tokens are sequence-sharded
        along `cp` so GSPMD partitions the chunk's forward pass across the
        context-parallel group — each device computes a slice of the query
        rows against the (replicated) gathered cache, which is exactly the
        ring schedule's per-device workload for one absolute-boundary
        chunk. Cache pool and outputs stay replicated over `cp`, so the
        scatter-back and sampled token are bit-identical to the
        single-device prefill. Buckets that don't divide evenly fall back
        to replicated tokens (no partial-shard padding ambiguity)."""
        if self.mesh is None:
            return repl
        cp = dict(self.mesh.shape).get("cp", 1)
        if cp > 1 and pbucket % cp == 0:
            from ... import sharding as _shardlib

            return _shardlib.named_sharding(self.mesh, (None, "cp"))
        return repl

    def _audit_ctx(self, pv):
        """Graph-auditor context for the step executables: on a TP mesh
        the parameters must STAY sharded (a full-size all-gather of a
        sharded weight means the rule table failed — GC001). None when
        the auditor is off, so compile_jit's hook stays free."""
        if not _gc.enabled():
            return None
        specs = {n: sh.spec for n, sh in (self._param_sh or {}).items()}
        return {"mesh": self.mesh, "param_avals": pv,
                "param_specs": specs,
                "expect_sharded_params": self.mesh is not None}

    # -- speculative decoding programs -------------------------------------
    def _draft_worst(self, plen, max_new):
        """Worst-case draft-pool blocks one sequence can ever hold: the
        draft writes rows `pos .. pos+K-1` per round with `pos` at most
        `plen + max_new - 2` (eligibility also caps rows below the table
        span, so `_nb` bounds it either way)."""
        return min(self._nb,
                   self.draft_pool.blocks_for(plen + max_new - 1 + self._k))

    def _d_weights(self):
        pv = {n: p._value for n, p in self._d_params.items()}
        bv = {n: b._value for n, b in self._d_buffers.items()}
        return pv, bv

    def _draft_weight_avals(self):
        import jax

        pv = {n: jax.ShapeDtypeStruct(tuple(p.shape), p._value.dtype)
              for n, p in self._d_params.items()}
        bv = {n: jax.ShapeDtypeStruct(tuple(b.shape), b._value.dtype)
              for n, b in self._d_buffers.items()}
        return pv, bv

    def _draft_shardings(self, n_scalars):
        """Fully-replicated (in, out) sharding tuples for the draft
        programs on a TP mesh (the draft is replicated by construction),
        else (None, None)."""
        if self.mesh is None:
            return None, None
        from ... import sharding as _shardlib

        repl = _shardlib.replicated(self.mesh)
        return (tuple([repl] * (3 + n_scalars)), (repl, repl))

    def _verify_fn(self, bucket, cached_only=False):
        """Target-side verification step for `bucket` sequences: scores
        K+1 positions per sequence — the last committed token plus the K
        draft proposals — as ONE chunk-shaped forward per sequence (the
        chunked-prefill idiom: tokens [1, K+1] at offset `pos`), inside
        one bucketed dispatch. The target's weights stream once per
        dispatch for all K+1 positions — on memory-bound decode hardware
        that is the whole speculative win — and each written KV row is
        scattered through the block table position-by-position with the
        decode step's own scatter.

        Bit-exactness: what gets COMMITTED is always the target's argmax,
        and the chunk forward's per-position argmax/KV must match the
        single-token decode step's — the same seq-chunk determinism
        chunked prefill (PR 13) already rests on and gates with its
        chunked-vs-monolithic bit-equality row; the speculative tier-1
        tests and the injector's decode-spec phase hold this verify step
        to the identical bar (bit-identity to `speculate_k=0` at every
        bucket size, int8 and prefix sharing included)."""
        fn = self._verify_fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        kk = self._k + 1

        def step(pv, bv, pool_ts, tokens, positions, tables):
            def seq_body(pool_ts, x):
                toks, pos0, table = x
                caches = self._gather(pool_ts, table)
                # speculation is greedy-only and adapter-free (submit
                # eligibility excludes both): the bare base model traces
                # — empty stacks are a static no-op in `wrapped`
                (logits, new_caches), _ = self._apply(
                    pv, bv, toks.reshape(1, kk), caches, pos0,
                    {}, jnp.int32(0))
                preds = jnp.argmax(
                    logits[0].astype(jnp.float32), -1).astype(jnp.int32)
                # the chunk wrote rows pos0..pos0+K: scatter each through
                # the table (pos0 is NOT block-aligned, so the prefill's
                # block-wise scatter does not apply — K+1 row scatters do)
                for j in range(kk):
                    pool_ts = self._scatter_row(pool_ts, new_caches,
                                                table, pos0 + j)
                return pool_ts, preds

            pool_ts, preds = jax.lax.scan(seq_body, pool_ts,
                                          (tokens, positions, tables))
            return pool_ts, preds

        pv, bv = self._weight_avals()
        avals = (pv, bv, self._avals(self.pool.tensors),
                 jax.ShapeDtypeStruct((bucket, kk), jnp.int32),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket, self._nb), jnp.int32))
        in_sh = out_sh = None
        sh = self._step_shardings()
        if sh is not None:
            pv_sh, bv_sh, pool_sh, repl = sh
            in_sh = (pv_sh, bv_sh, pool_sh, repl, repl, repl)
            out_sh = (pool_sh, repl)
        compiled, source = aot.compile_jit(
            step, avals, cached_only=cached_only,
            fingerprint=self._fingerprint, cache=self._cache,
            tag=f"decode-verify-b{bucket}",
            extra_key=("speculate_k", self._k),
            in_shardings=in_sh, out_shardings=out_sh,
            donate_argnums=(2,), audit_ctx=self._audit_ctx(pv))
        return self._installed(self._verify_fns, bucket, compiled, source)

    def _propose_fn(self, bucket, cached_only=False):
        """Draft-side proposal step for `bucket` sequences: K
        autoregressive draft decode steps fused into ONE dispatch — each
        iteration feeds its own argmax back in, writing the draft's KV
        rows through the draft block table. Draft numerics only gate the
        ACCEPTANCE RATE, never the committed output (only target-argmax
        tokens are ever committed), so the draft program needs no
        bit-stability argument.

        The scan runs K+1 iterations, not K: the extra step feeds the
        LAST proposal back in (its output is discarded) purely to write
        draft KV row `pos+K` — after a fully-accepted (bonus) round the
        committed position advances by K+1 and every draft row behind it
        must be valid, or the next proposal would attend a never-written
        row and acceptance would silently erode. Rows written past the
        committed position on a partial acceptance are garbage behind
        the rollback line: the next round rewrites each before any query
        can attend it (a row's own write precedes its first read)."""
        fn = self._propose_fns.get(bucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        k = self._k

        def step(pv, bv, pool_ts, tokens, positions, tables):
            def seq_body(pool_ts, x):
                tok0, pos0, table = x

                def tok_body(carry, pos):
                    pool_ts, tok = carry
                    caches = self._gather(pool_ts, table)
                    (logits, new_caches), _ = self._d_apply(
                        pv, bv, tok.reshape(1, 1), caches, pos)
                    nxt = jnp.argmax(
                        logits[0, -1].astype(jnp.float32),
                        -1).astype(jnp.int32)
                    pool_ts = self._scatter_row(pool_ts, new_caches,
                                                table, pos)
                    return (pool_ts, nxt), nxt

                poss = pos0 + jnp.arange(k + 1, dtype=jnp.int32)
                (pool_ts, _), props = jax.lax.scan(
                    tok_body, (pool_ts, tok0), poss)
                return pool_ts, props[:k]

            pool_ts, props = jax.lax.scan(seq_body, pool_ts,
                                          (tokens, positions, tables))
            return pool_ts, props

        pv, bv = self._draft_weight_avals()
        avals = (pv, bv, self._avals(self.draft_pool.tensors),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket,), jnp.int32),
                 jax.ShapeDtypeStruct((bucket, self._nb), jnp.int32))
        # K only shows in the OUTPUT shape: without extra_key two engines
        # with different speculate_k would collide on identical input
        # avals in the persistent cache
        in_sh, out_sh = self._draft_shardings(3)
        compiled, source = aot.compile_jit(
            step, avals, cached_only=cached_only,
            fingerprint=self._draft_fingerprint,
            cache=self._cache, tag=f"decode-propose-b{bucket}",
            extra_key=("speculate_k", self._k),
            in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(2,),
            audit_ctx=None if not _gc.enabled() else {"mesh": self.mesh})
        return self._installed(self._propose_fns, bucket, compiled, source)

    def _draft_prefill_fn(self, pbucket, cached_only=False):
        """Draft catch-up prefill: the draft-model twin of `_prefill_fn`
        (chunk-aware, block-scattered, extended table) used to (re)build
        the draft's KV over already-COMMITTED tokens — at first
        speculation (the prompt), after a prefix-cache full hit (the
        draft never saw the prompt), and after a plain-decode fallback
        advanced the sequence without the draft."""
        fn = self._draft_prefill_fns.get(pbucket)
        if fn is not None:
            return fn
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        nb_table = self._nb + self._prefill_tail
        prefill = self._make_prefill_body(pbucket, self._d_apply)
        pv, bv = self._draft_weight_avals()
        avals = (pv, bv, self._avals(self.draft_pool.tensors),
                 jax.ShapeDtypeStruct((1, pbucket), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((nb_table,), jnp.int32))
        in_sh, out_sh = self._draft_shardings(4)
        compiled, source = aot.compile_jit(
            prefill, avals, cached_only=cached_only,
            fingerprint=self._draft_fingerprint,
            cache=self._cache, tag=f"decode-prefill-p{pbucket}",
            in_shardings=in_sh, out_shardings=out_sh, donate_argnums=(2,),
            audit_ctx=None if not _gc.enabled() else {"mesh": self.mesh})
        return self._installed(self._draft_prefill_fns, pbucket, compiled,
                               source)

    def _cow_fn(self, cached_only=False):
        """Compiled copy-on-write block copy: ONE donated dispatch that
        rewrites a single block's rows across every layer tensor. With
        the pool donated, XLA aliases input to output buffers, so the
        copy costs one block's traffic — an eager per-tensor `at[].set`
        would functionally re-materialize the ENTIRE pool per COW, a
        per-admission latency spike scaling with pool size."""
        if "cow" in self._pool_fns:
            return self._pool_fns["cow"]
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        def cow(pool_ts, src, dst):
            return [tuple(t.at[dst].set(t[src]) for t in layer)
                    for layer in pool_ts]

        avals = (self._avals(self.pool.tensors),
                 jax.ShapeDtypeStruct((), jnp.int32),
                 jax.ShapeDtypeStruct((), jnp.int32))
        in_sh = out_sh = None
        sh = self._step_shardings()
        if sh is not None:
            _, _, pool_sh, repl = sh
            in_sh = (pool_sh, repl, repl)
            out_sh = pool_sh
        compiled, source = aot.compile_jit(
            cow, avals, cached_only=cached_only,
            fingerprint=self._fingerprint, cache=self._cache,
            tag="decode-cow-copy", donate_argnums=(0,),
            in_shardings=in_sh, out_shardings=out_sh,
            audit_ctx=None if not _gc.enabled() else {"mesh": self.mesh})
        return self._installed(self._pool_fns, "cow", compiled, source)

    def _zero_fn(self, cached_only=False):
        """Compiled zeroing of one state slot across the recurrent layers:
        ONE donated dispatch over those layers' tensors alone (the blocks
        are not passed), aliased in place like `_cow_fn`'s copy."""
        if "zero" in self._pool_fns:
            return self._pool_fns["zero"]
        import jax
        import jax.numpy as jnp
        from ...jit import aot

        def zero(state_ts, slot):
            return [tuple(t.at[slot].set(jnp.zeros(t.shape[1:], t.dtype))
                          for t in layer) for layer in state_ts]

        avals = (self._avals(self._state_tensors()),
                 jax.ShapeDtypeStruct((), jnp.int32))
        compiled, source = aot.compile_jit(
            zero, avals, cached_only=cached_only,
            fingerprint=self._fingerprint, cache=self._cache,
            tag="decode-zero-slot", donate_argnums=(0,),
            audit_ctx=None if not _gc.enabled() else {"mesh": self.mesh})
        return self._installed(self._pool_fns, "zero", compiled, source)

    def _state_tensors(self):
        return [layer for i, layer in enumerate(self.pool.tensors)
                if self._slot_layer(i)]

    def _zero_slot(self, slot):
        """A slot's state and window back to zero: what position 0 starts
        from, whoever held the slot before."""
        zeroed = iter(self._zero_fn()(self._state_tensors(),
                                      np.asarray(slot, np.int32)))
        self.pool.tensors = [
            next(zeroed) if self._slot_layer(i) else layer
            for i, layer in enumerate(self.pool.tensors)]

    def warmup(self):
        """Compile (or disk-load) every decode bucket and prefill bucket
        (plus the COW block-copy when prefix sharing is on) up front, so
        traffic never stalls on XLA — and so the tpu-san retrace
        sentinel can treat any later compile as a finding. Returns
        ``{"decode": [...], "prefill": [...]}``.

        The warm set is what the scheduler can dispatch. With chunked
        prefill on that leaves out the target's prefill buckets above the
        chunk: a dispatch holds at most a chunk of a prompt, so no prompt
        ever reaches them (`_prefill_chunk`), and the largest are the
        costliest programs to build and to load. The draft's catch-up does
        use them. `"prefill"` lists the buckets that were brought up.

        What the persistent cache holds is loaded first, one program after
        the other on the calling thread: measured on a TPU v5e, a load made
        from a pool's thread takes ten times as long as the same load here,
        whether or not others run beside it (PERF.md section 6, PR 35).
        What it lacks is built on a thread pool, joined before this
        returns: no program depends on another, the Python traces take
        turns (`jit/aot._trace_lock`) and the XLA compilations, which are
        most of a cold start, run side by side. Under the
        collective-schedule auditor the walk is serial: its cross-host
        verifier hashes the ORDER in which programs are built."""
        # one step executable a bucket either way
        step_fn = self._decode_fn if self._bd is None else self._bd_fn
        programs = [(step_fn, b) for b in self.decode_buckets]
        chunks = [p for p in self.prefill_buckets
                  if not self._chunk or p <= self._chunk]
        programs += [(self._prefill_fn, p) for p in chunks]
        if self._prefix_on:
            programs.append((self._cow_fn,))
        if self._recurrent:
            programs.append((self._zero_fn,))
        out = {"decode": list(self.decode_buckets), "prefill": chunks}
        if self._spec_on:
            # speculation executables are part of the warm set too: a
            # propose/verify/catch-up dispatch after mark_warm() that
            # compiles is a retrace finding exactly like a decode one
            for b in self.decode_buckets:
                programs += [(self._propose_fn, b), (self._verify_fn, b)]
            programs += [(self._draft_prefill_fn, p)
                         for p in self.prefill_buckets]
            out["speculate_k"] = self._k
        to_build = [(fn, *args) for fn, *args in programs
                    if fn(*args, cached_only=True) is None]
        if to_build:
            width = 1 if _cc.enabled() else \
                min(len(to_build), os.cpu_count() or 1)
            with concurrent.futures.ThreadPoolExecutor(
                    width, thread_name_prefix="DecodeEngine-warmup") as ex:
                for done in [ex.submit(fn, *args)
                             for fn, *args in to_build]:
                    done.result()   # a failed build raises here, as ever
        return out

    def _san_sweep(self, pool_ts):
        """tpu-san non-finite guard over the freshly written KV pool: a
        NaN/Inf born in the step's logits lands in the cache rows it
        wrote, so this per-dispatch sweep blames the first poisoned
        layer/tensor (quantized int leaves are skipped; their f32 scale
        leaves are checked). Runs on the step-pool member thread so a
        hit fails THIS step through the existing typed-error and
        isolation machinery. Free unless PADDLE_TPU_SAN=1."""
        if not _san.enabled():
            return
        _san.check_finite(
            "decode.step",
            ((f"kv_pool/layer{i}/t{j}", t)
             for i, layer in enumerate(pool_ts)
             for j, t in enumerate(layer)))

    # -- scheduler ---------------------------------------------------------
    def _weights(self):
        pv = {n: p._value for n, p in self._params.items()}
        bv = {n: b._value for n, b in self._buffers.items()}
        return pv, bv

    #: samp-pack values for a padded (or greedy) batch row — the raw-
    #: argmax lane, so padding never perturbs anything
    _PACK_DEFAULTS = {"ctr": 0, "greedy": 1, "rep": 1.0, "seed": 0,
                      "temp": 1.0, "top_k": 0, "top_p": 1.0}

    def _pack_values(self, seq):
        """This sequence's samp-pack scalars for the NEXT token. The RNG
        counter is the token's absolute output index (committed tokens
        from a prior attempt included), so a restarted or failed-over
        sequence redraws the identical stream."""
        sp = seq.sampling
        if sp is None or sp.is_greedy():
            # greedy: the raw-argmax lane, every other knob inert (the
            # SamplingParams contract: temperature <= 0 means argmax)
            return dict(self._PACK_DEFAULTS,
                        ctr=seq.sample_base + seq.generated)
        return {"ctr": seq.sample_base + seq.generated, "greedy": 0,
                "rep": sp.repetition_penalty, "seed": sp.seed,
                "temp": sp.temperature, "top_k": sp.top_k,
                "top_p": sp.top_p}

    def _samp_row(self, seq):
        """Scalar samp pack (the single-sequence prefill dispatch)."""
        from ..sampling import PACK_FIELDS

        vals = self._pack_values(seq)
        return {name: np.asarray(vals[name], np.dtype(dt))
                for name, dt in PACK_FIELDS}

    def _samp_pack(self, seqs, bucket):
        """Batched `(bucket,)` samp pack for one decode dispatch —
        param mixes land here as VALUES; the layout never changes."""
        from ..sampling import PACK_FIELDS

        rows = [self._pack_values(s) for s in seqs]
        pack = {}
        for name, dt in PACK_FIELDS:
            arr = np.full(bucket, self._PACK_DEFAULTS[name],
                          np.dtype(dt))
            for i, r in enumerate(rows):
                arr[i] = r[name]
            pack[name] = arr
        return pack

    @staticmethod
    def _is_greedy(seq):
        """True when this sequence's next token is the raw-logits argmax
        (no params, or temperature <= 0): the full-prompt prefix-cache
        fast path — delivering the PUBLISHER's cached next token — is
        exact for these and only these."""
        return seq.sampling is None or seq.sampling.is_greedy()

    def _hist_fill(self, row, seq):
        sp = seq.sampling
        if sp is not None and not sp.is_greedy() \
                and sp.repetition_penalty != 1.0:
            toks = self._committed_tokens(seq)
            row[: len(toks)] = toks

    def _hist_row(self, seq):
        """Token history `(max_length,)` (-1 padded) for the repetition
        penalty — filled only when the sequence actually penalizes
        (values, not signatures; an all-(-1) row is the identity)."""
        row = np.full(self.max_length, -1, np.int32)
        self._hist_fill(row, seq)
        return row

    def _hist_pack(self, seqs, bucket):
        rows = np.full((bucket, self.max_length), -1, np.int32)
        for i, s in enumerate(seqs):
            self._hist_fill(rows[i], s)
        return rows

    def _padded_table(self, seq, length=None):
        # 0 = reserved padding sink
        table = np.zeros(self._nb if length is None else length, np.int32)
        table[: len(seq.blocks)] = seq.blocks
        return table

    def _submit_step(self, run, names, seqs):
        """Dispatch a step closure on the supervised step pool. A wedged
        dispatch (pool hang detection fired: worker retired, capacity
        restored) is re-submitted: the closure takes the pool as it
        stands when it reaches its compiled call (`_consume`) and all
        else from the last COMMITTED state, so a re-run is safe and
        batchmates lose nothing; the attempt given up is cancelled first,
        so its worker, should it wake, dispatches nothing. An attempt that
        timed out INSIDE its compiled call is waited for instead (it holds
        the pool, and what it brings back is the step). `RequestFailed` /
        `PoolClosed` propagate to the caller for classification, unless
        the failure cost the pool: then the cache is rebuilt for every
        resident sequence (`_rebuild_pools`; `seqs` are the dispatch's
        members) and `_PoolRebuilt` ends the round.

        Each attempt is one `.handoff` span of the round's phase
        (`names`): `run(member, ctx, attempt)` opens its `.enqueue` /
        `.fetch` under `ctx` on the worker, so the span's self time is the
        way to the worker and back. The step pool itself is entered
        detached: its admission must not add spans of its own to the
        round."""
        try:
            last = attempt = None
            for _ in range(self._step_retries + 1):
                with _otrace.span(names["handoff"], profile=True) as hand:
                    if attempt is not None and attempt.claimed:
                        # the attempt that timed out is inside its compiled
                        # call, with the pool: there is nothing to dispatch
                        # from until it comes back, and what it brings
                        # back is the step
                        if attempt.done.wait(self.step_timeout):
                            if attempt.error is not None:
                                raise RequestFailed(
                                    f"decode step failed past its "
                                    f"deadline: {attempt.error}",
                                    cause=attempt.error)
                            return attempt.result
                        with self._lock:
                            self._wedged_steps += 1
                        continue
                    attempt = _Attempt()

                    def call(member, ctx=hand.ctx, attempt=attempt):
                        _otrace.reserve_ring()   # the worker keeps a window
                        attempt.error = None     # the step pool's own retry
                        try:
                            attempt.result = run(member, ctx, attempt)
                        except BaseException as e:
                            attempt.error = e
                            raise
                        finally:
                            attempt.done.set()
                        return attempt.result

                    with _otrace.detached():
                        req = self._steps.submit(call,
                                                 timeout=self.step_timeout)
                    try:
                        return req.result()
                    except DeadlineExceeded as e:
                        with self._lock:
                            attempt.cancelled = not attempt.claimed
                            self._wedged_steps += 1
                        last = e
            raise RequestFailed(
                f"decode step wedged {self._step_retries + 1} time(s) — "
                f"giving up", cause=last,
                attempts=self._step_retries + 1)
        except PoolClosed:
            raise
        except Exception as e:  # noqa: BLE001 — whatever failed: did it
            if not self._pool_lost():       # take the pool with it?
                raise
            self._rebuild_pools(seqs, e)
            raise _PoolRebuilt(str(e)) from e

    def _consume(self, attempt, pool, call):
        """`call(pool_tensors)`, the compiled program that takes `pool`
        donated, on the step-pool worker. The tensors are read here and
        not when the closure was made: a re-submitted closure then starts
        from the pool as the engine holds it now. Taking them and finding
        the attempt live is one step under the engine's lock, where
        `_submit_step` cancels an attempt, so a retired worker can never
        take a pool that a later attempt made."""
        with self._lock:
            if attempt.cancelled:
                raise _AttemptRetired(
                    "this attempt was given up as wedged; the step was "
                    "submitted again")
            attempt.claimed = True
            pool_ts = pool.tensors
        out = call(pool_ts)
        if _san.enabled():
            # whoever still holds the old list holds deleted arrays: a
            # later use names this site, not jax's "Array has been deleted"
            _san.note_donation("decode.dispatch", pool_ts, tag=pool.name)
        with self._lock:
            self._donated_dispatches += 1
        return out

    def _pool_lost(self):
        """Whether a buffer of the cache is gone: a compiled call consumed
        it and did not come back with its successor."""
        pools = [self.pool] + ([self.draft_pool] if self._spec_on else [])
        return any(t.is_deleted() for pool in pools
                   for layer in pool.tensors for t in layer)

    def _rebuild_pools(self, seqs, cause):
        """Fault contract (c), on the scheduler thread: a fresh cache, and
        every resident sequence back at the head of the waiting queue with
        no block to its name, to be admitted and prefilled again from its
        committed tokens as a resumed request is (`submit`'s
        `resume_committed`): the final chunk samples the NEXT token, under
        the counter the decode step would have used, so a greedy sequence
        goes on bit for bit and a sampled one redraws its own stream. A
        block model keeps its open block, which lives on the host. The
        prefix cache's entries go with the rows they named. `seqs`, the
        members of the dispatch that failed, are suspects: each decodes
        alone until a step of its own has come back, and a suspect that
        fails alone is failed."""
        if len(seqs) == 1 and seqs[0].suspect:
            self._finish(seqs[0], "failed", RequestFailed(
                f"sequence {seqs[0].id}: its dispatch failed alone, twice, "
                f"after consuming the pool: {type(cause).__name__}: "
                f"{cause}", cause=cause))
        with self._cv:
            self._pool_rebuilds += 1
            resident = sorted(self._active + self._prefill_q,
                              key=lambda s: s.id)
            self._clear_prefix_cache_locked()
            for seq in resident:
                self.pool.free_owned(seq.id)
                seq.blocks = []
                seq.outstanding = 0
                seq.prefill_pos = seq.matched_tokens = 0
                if seq.state == _ACTIVE:
                    ids = self._committed_tokens(seq)
                    seq.prefill_ids = ids if self._bd is None \
                        else ids[:seq.pos]
                if self._spec_on:
                    self.draft_pool.free_owned(seq.id)
                    seq.draft_blocks = []
                    seq.draft_pos = seq.draft_outstanding = 0
                seq.state = _WAITING
            self._active = []
            self._prefill_q = []
            self._waiting = resident + self._waiting
            self.pool.reset_tensors()
            if self._spec_on:
                self.draft_pool.reset_tensors()
                self._place_draft_pool()
        for seq in seqs:
            seq.suspect = True
        _log.warning(
            "decode engine %s: a dispatch of sequences %s failed after it "
            "consumed the pool (%s: %s); cache rebuilt, %d resident "
            "sequence(s) prefill again from their committed tokens",
            self.name, [s.id for s in seqs], type(cause).__name__, cause,
            len(resident))

    def _loop(self):
        """The scheduler thread. Its time is tiled by `decode.round`
        roots (an iteration that found work) and `decode.idle_wait`
        roots (the stretch it slept with nothing to do); a round starts
        at the loop top, so the wait for the engine's lock is inside
        its `.admit` phase and nothing between two rounds is unnamed."""
        if _otrace.enabled():
            _otrace.reserve_ring()
            _otrace.watch_gc()
        idle = None
        while True:
            t_top = time.perf_counter()
            with self._cv:
                if self._stopping:
                    break
                if not self._waiting and not self._active \
                        and not self._prefill_q:
                    if self._closed:
                        break
                    if idle is None:
                        idle = (_otrace.root_span(_IDLE, profile=True,
                                                  t0=t_top), t_top)
                    self._cv.wait(0.05)
                    continue
                counts = (len(self._active), len(self._prefill_q),
                          len(self._waiting))
                oldest = self._waiting[0].t_submit if self._waiting \
                    else None
            if idle is not None:
                t_top = self._end_idle(idle, oldest)
                idle = None
            self._round(t_top, counts)
        if idle is not None:
            idle[0].end()

    def _end_idle(self, idle, oldest):
        """Close a `decode.idle_wait`. Work that sat in the queue while
        the loop slept (`work_waited_s`: normally the wake-up latency of
        one notify) is judged like a round: an idle wait is slow only by
        the part of it that had work."""
        sp, t0 = idle
        now = time.perf_counter()
        waited = 0.0 if oldest is None else \
            max(0.0, now - max(oldest, t0))
        sp.set_attr("work_waited_s", waited)
        sp.end()
        if waited > _SLOW_ROUND_S:
            self._judge_round(sp, waited, f"idle wait before round "
                                          f"{self._round_no + 1}")
        return now

    def _round(self, t0, counts):
        self._round_no += 1
        self._last_step = None
        root = _otrace.root_span(
            _ROUND, attrs={"round": self._round_no, "active": counts[0],
                           "prefilling": counts[1], "waiting": counts[2]}
            if _otrace.enabled() else None, profile=True, t0=t0)
        try:
            with root:
                with _otrace.span(_ADMIT, profile=True, t0=t0):
                    self._sweep_waiting()
                    self._admit_waiting()
                    self._sweep_prefilling()
                # ONE prefill chunk per round, interleaved with the
                # decode step below: a long prompt advances chunk by
                # chunk while the running batch keeps streaming tokens
                if self._prefill_q:
                    with _otrace.span(_PREFILL["round"],
                                      profile=True):
                        self._prefill_round()
                if self._active:
                    with _otrace.span(_DECODE["round"],
                                      profile=True) as phase:
                        self._decode_round(phase)
        except _PoolRebuilt:
            pass    # every sequence waits again; the next round admits them
        except Exception as exc:  # noqa: BLE001 — scheduler must
            # survive anything: fail the implicated sequences with a
            # typed error instead of silently dying with them stuck
            err = RequestFailed(
                f"decode scheduler error: {type(exc).__name__}: {exc}",
                cause=exc)
            for seq in list(self._active) + list(self._prefill_q):
                self._finish(seq, "failed", err)
        took = time.perf_counter() - t0
        if took > _SLOW_ROUND_S:
            self._judge_round(root, took, f"round {self._round_no}")
        self._round_times.append(took)

    def _judge_round(self, span, took, what):
        """Rare path (a round or an in-work idle wait past
        `_SLOW_ROUND_S`): past `_SLOW_ROUND_X` times the median of the
        last rounds it is pinned in the flight recorder (`slow_round`),
        counted, and logged once with its phases — so the run that meets
        a stall names the phase in its own log, traced or not."""
        if not self._round_times:
            return
        usual = statistics.median(self._round_times)
        if took <= _SLOW_ROUND_X * usual:
            return
        with self._lock:
            self._slow_rounds += 1
        phases = {}
        if span.ctx is not None:
            rec = _flight.recorder().pin(span.ctx.trace_id,
                                         reason="slow_round")
            for sp in rec["spans"]:
                phases[sp.name] = round(
                    phases.get(sp.name, 0.0) + sp.t1 - sp.t0, 4)
            t1 = time.perf_counter()
            for sp in _flight.recorder().spans_between(
                    t1 - took, t1, prefix="host.gc")[0]:
                phases["host.gc"] = round(
                    phases.get("host.gc", 0.0) + sp.t1 - sp.t0, 4)
        bucket, members = self._last_step or (None, [])
        _log.warning(
            "decode engine %s: slow %s: %.3f s against a median of %.3f s "
            "over the last %d rounds; phases (s) %s; bucket %s; members "
            "%s; trace %s", self.name, what, took, usual,
            len(self._round_times), phases, bucket, members,
            span.trace_id_hex)

    def _sweep_waiting(self):
        with self._cv:
            keep = []
            for seq in self._waiting:
                if seq.cancelled:
                    self._finish_locked(seq, "cancelled", PoolClosed(
                        f"sequence {seq.id} cancelled before prefill"))
                elif seq.deadline.expired():
                    self._finish_locked(seq, "timed_out", DeadlineExceeded(
                        f"sequence {seq.id} expired in the waiting queue"))
                else:
                    keep.append(seq)
            self._waiting = keep

    def _admit_waiting(self):
        """Move waiting sequences toward the running batch at this step
        boundary: capacity = a free batch slot AND enough free blocks to
        cover the newcomer's worst-case FRESH growth (worst case minus
        whatever a prefix-cache hit lets it share, plus one COW block
        when a shared prompt tail ends mid-block) on top of every live
        sequence's remaining worst-case growth — so lazy per-step block
        allocation can never fail mid-flight. Under pressure, LRU
        prefix-cache entries are evicted to make headroom."""
        while True:
            with self._cv:
                if self._stopping or not self._waiting:
                    return
                if len(self._active) + len(self._prefill_q) \
                        >= self.max_active:
                    return
                seq = self._waiting[0]
                plen = len(seq.prefill_ids)
                cow = 1 if (self._prefix_on
                            and plen % self.block_size) else 0
                seq.reserved_total = self.pool.blocks_for(
                    self._cache_rows(len(seq.prompt), seq.max_new)) + cow
                entry = self._match_prefix(
                    seq.prefill_ids, seq.adapter_sig,
                    full_ok=self._is_greedy(seq)) \
                    if self._prefix_on else None
                matched = len(entry["blocks"]) if entry else 0
                reserve = sum(s.outstanding for s in self._active) \
                    + sum(s.outstanding for s in self._prefill_q)
                fresh = seq.reserved_total - matched
                if self.pool.free_count < reserve + fresh \
                        and not self._evict_for(reserve + fresh,
                                                keep=entry):
                    return      # not enough headroom yet; retry next round
                if self._spec_on:
                    # the draft pool has no prefix cache to evict from:
                    # its worst case (every live sequence speculating K
                    # tokens past its final position) must simply fit
                    dworst = self._draft_worst(len(seq.prompt), seq.max_new)
                    dreserve = sum(s.draft_outstanding
                                   for s in self._active) \
                        + sum(s.draft_outstanding
                              for s in self._prefill_q)
                    if self.draft_pool.free_count < dreserve + dworst:
                        return  # draft headroom pending; retry next round
                    seq.draft_outstanding = dworst
                self._waiting.pop(0)
            try:
                self._begin_sequence(seq, entry)
            except Exception as exc:  # noqa: BLE001 — the sequence is in
                # neither _waiting nor _prefill_q nor _active here, so an
                # unexpected error must fail it HERE or its stream hangs
                # and its blocks leak
                self._finish(seq, "failed", RequestFailed(
                    f"sequence {seq.id}: prefill error: "
                    f"{type(exc).__name__}: {exc}", cause=exc))

    def _begin_sequence(self, seq, entry):
        """Attach an admitted sequence to its prefix-cache hit (bumping
        refcounts instead of re-prefilling the shared tokens) and route
        it: a full-prompt hit joins the running batch immediately — zero
        prompt compute — anything else enters the chunked-prefill queue."""
        plen = len(seq.prefill_ids)
        again = seq.round_admit is not None     # the cache was rebuilt
        seq.t_admit = time.perf_counter()
        seq.round_admit = self._round_no
        if self._recurrent:
            # its state's slot (as many slots as batch slots: admission has
            # just found one of those), zeroed of its last owner's state
            seq.slot = self.pool.alloc_slot(seq.id)
            self._zero_slot(seq.slot)
        if self._h_queue_wait is not None and seq.submitted_at is not None \
                and not again:
            self._h_queue_wait.observe(self._clock() - seq.submitted_at,
                                       ctx=seq.span.ctx)
        if entry is not None:
            self.pool.incref(entry["blocks"], owner=seq.id)
            seq.blocks = list(entry["blocks"])
            seq.prefill_pos = seq.matched_tokens = entry["t"]
            with self._cv:
                self._prefix_hits += 1
                self._prefix_tokens_reused += entry["t"]
                if entry["t"] == plen:
                    self._prefix_full_hits += 1
        elif self._prefix_on:
            with self._cv:
                self._prefix_misses += 1
        seq.outstanding = seq.reserved_total - len(seq.blocks)
        if seq.prefill_pos == plen:
            # complete prefix: the whole prompt (and its next token) is
            # cached — the sequence starts decoding this very round
            seq.state = _ACTIVE
            seq.pos = plen
            with self._cv:
                self._active.append(seq)
                self._peak_resident = max(
                    self._peak_resident,
                    len(self._active) + len(self._prefill_q))
            if self._bd is not None:
                # nothing to deliver yet: the first block opens (a prompt
                # shorter than a block has no prefill at all); a sequence
                # whose cache was rebuilt has its open block still
                if seq.bd is None:
                    self._bd_open_block(seq, seq.prompt[plen:])
            else:
                self._deliver(seq, int(entry["next_token"]))
            return
        seq.state = _PREFILL
        with self._cv:
            self._prefill_q.append(seq)
            self._peak_resident = max(
                self._peak_resident,
                len(self._active) + len(self._prefill_q))

    def _sweep_prefilling(self):
        with self._cv:
            for seq in list(self._prefill_q):
                if seq.cancelled:
                    self._finish_locked(seq, "cancelled", PoolClosed(
                        f"sequence {seq.id} cancelled during prefill"))
                elif seq.deadline.expired():
                    self._finish_locked(seq, "timed_out", DeadlineExceeded(
                        f"sequence {seq.id} expired during prefill"))

    def _prefill_round(self):
        """Run ONE prefill chunk for the queued sequence with the fewest
        remaining prompt tokens (shortest-remaining-first: a short prompt
        is never stuck behind a 1024-token monolith — the head-of-line
        fix chunking exists for). Faults implicate only that sequence."""
        with self._cv:
            if self._stopping or not self._prefill_q:
                return
            seq = min(self._prefill_q, key=lambda s: (
                len(s.prefill_ids) - s.prefill_pos, s.id))
        try:
            self._prefill_chunk(seq)
        except PoolClosed as e:
            self._finish(seq, "cancelled", e)
        except RequestFailed as e:
            self._finish(seq, "failed", e)
        except _PoolRebuilt:
            raise
        except Exception as exc:  # noqa: BLE001 — e.g. an XLA compile
            # failure: fail THIS sequence, not the scheduler
            self._finish(seq, "failed", RequestFailed(
                f"sequence {seq.id}: prefill error: "
                f"{type(exc).__name__}: {exc}", cause=exc))

    def _prefill_chunk(self, seq):
        """Dispatch the next prompt chunk of `seq` (the whole remainder
        when chunking is off or the prompt fits one chunk). On the final
        chunk the sequence publishes its prefix-cache entries and joins
        the running batch."""
        names = _PREFILL
        plen = len(seq.prefill_ids)
        start = seq.prefill_pos
        remaining = plen - start
        this_len = self._chunk if (self._chunk
                                   and remaining > self._chunk) \
            else remaining
        if this_len > self.prefill_buckets[-1]:
            # only a sequence whose cache is being rebuilt from its
            # committed tokens (`_rebuild_pools`) can have outgrown the
            # largest bucket, and only with chunking off
            raise RequestFailed(
                f"sequence {seq.id}: {this_len} committed tokens to "
                f"prefill again, over the largest prefill bucket "
                f"{self.prefill_buckets[-1]}, and chunked prefill is off")
        pbucket = next(p for p in self.prefill_buckets if p >= this_len)
        with _otrace.span(names["pack"], profile=True):
            # fresh blocks to hold positions
            # [len(blocks)*bs, start+this_len)
            need = self.pool.blocks_for(start + this_len) - len(seq.blocks)
            if need > 0:
                try:
                    seq.blocks += self.pool.alloc(need, owner=seq.id)
                    seq.outstanding -= need
                except OutOfBlocks as e:
                    # the admission gate guarantees this can't happen —
                    # an over-admission bug
                    raise RequestFailed(
                        f"sequence {seq.id}: block pool exhausted at "
                        f"prefill", cause=e) from e
            fn = self._prefill_fn(pbucket)
            pv, bv = self._weights()
            ats = self._adapter_stacks()
            aid = np.asarray(seq.adapter_slot, np.int32)
            hist = self._hist_row(seq)
            samp = self._samp_row(seq)
            tokens = np.full((1, pbucket), self.pad_token_id, np.int32)
            tokens[0, :this_len] = seq.prefill_ids[start:start + this_len]
            table = self._padded_table(seq, self._nb + self._prefill_tail)
            extra = (np.asarray(seq.slot, np.int32),) \
                if self._recurrent else ()
        hook = self._fault_hook
        sctx = seq.span.ctx
        chunked = this_len < remaining or start > 0
        rnd = self._round_no
        lin = self._layer_attrs()

        def run(_member, hctx, attempt):
            if hook is not None:
                hook("prefill", [seq.id], {"bucket": pbucket,
                                           "start": start,
                                           "tokens": this_len})
            # chunk span in the SEQUENCE's trace (the step-pool worker
            # thread re-enters the sequence context explicitly), so a
            # chunked TTFT decomposes chunk by chunk in /traces/<id>;
            # `round` joins it to the scheduler round that submitted it
            with _otrace.span_in(
                    "decode.prefill_chunk" if chunked
                    else "decode.prefill", sctx,
                    attrs=None if sctx is None else
                    {"seq": seq.id, "bucket": pbucket, "start": start,
                     "tokens": this_len, "prompt_len": plen,
                     "round": rnd, **lin}, profile=True), \
                    _locks.blocking_region("decode.step_dispatch"):
                # the hot-sync probe covers the dispatch only; the token
                # readback below is the step's deliverable (streaming
                # needs the committed value on the host) and is
                # sanctioned inside the step pool's serving.execute
                # region
                with _san.hot_region("decode.step_dispatch"), \
                        _otrace.span_in(names["enqueue"], hctx,
                                        profile=True):
                    new_pool, nxt = self._consume(
                        attempt, self.pool, lambda pool_ts: fn(
                            pv, bv, ats, pool_ts, tokens,
                            np.asarray(start, np.int32),
                            np.asarray(this_len, np.int32),
                            table, aid, hist, samp, *extra))
                self._san_sweep(new_pool)
                with _san.allow_host_sync("decode.token_fetch"), \
                        _otrace.span_in(names["fetch"], hctx,
                                        profile=True):
                    return new_pool, np.asarray(nxt).reshape(-1)

        new_pool, out = self._submit_step(run, names, [seq])
        tok = int(self._take_expert_counts(out, 1, this_len, pbucket,
                                           chunk=True)[0])
        if self._recurrent:
            with self._lock:
                if pbucket > 1:
                    self._lin_chunk_tokens += this_len
                else:
                    self._lin_step_tokens += this_len
        with _otrace.span(names["deliver"], profile=True):
            self._prefill_done(seq, new_pool, tok, start + this_len)

    def _prefill_done(self, seq, new_pool, tok, done):
        """Commit one prompt chunk: the pool, the prefix-cache entries it
        completes and, after the last chunk, the first token (block
        diffusion: the first open block)."""
        plen = len(seq.prefill_ids)
        self.pool.tensors = new_pool
        seq.prefill_pos = done
        seq.chunks += 1
        with self._lock:
            self._prefill_chunks += 1
        if self._prefix_on and self._chunk and done % self._chunk == 0 \
                and (self._is_greedy(seq) or done < plen):
            # a full chunk boundary: publish tokens[0:done] for reuse —
            # chunk boundaries are absolute multiples of the chunk size,
            # so any later prompt sharing these tokens computes (or now
            # skips) the IDENTICAL dispatches, keeping reuse bit-exact.
            # A SAMPLED sequence's final chunk is not published: its
            # stored next_token is a draw from this request's RNG, and a
            # full-prompt hit would deliver it to someone else.
            with self._cv:
                self._prefix_insert(
                    "chunk", seq.prefill_ids[:done],
                    seq.blocks[:done // self.block_size], tok,
                    seq.adapter_sig)
        if done < plen:
            return
        # prompt complete: publish the full-prompt entry (identical
        # resubmissions skip prefill entirely; a mid-block tail is shared
        # too — the writer COW-copies it before its first private token),
        # then join the running batch and stream the first token. Only
        # greedy sequences publish full entries (same RNG argument as
        # above); cache keys carry the adapter signature, so KV computed
        # under one adapter version is never reused under another.
        if self._prefix_on and self._is_greedy(seq) \
                and not (self._chunk and plen % self._chunk == 0):
            with self._cv:
                self._prefix_insert("full", seq.prefill_ids, seq.blocks,
                                    tok, seq.adapter_sig)
        with self._lock:
            self._prefills += 1
        seq.state = _ACTIVE
        seq.pos = plen
        with self._cv:
            if seq in self._prefill_q:
                self._prefill_q.remove(seq)
            self._active.append(seq)
        if self._bd is not None:
            # the prefill's own next token is no token of a block model:
            # the prompt's remainder opens the first block instead (a
            # sequence whose cache was rebuilt has its open block still)
            if seq.bd is None:
                self._bd_open_block(seq, seq.prompt[plen:])
        else:
            self._deliver(seq, tok)

    # -- prefix cache (copy-on-write block sharing) ------------------------
    # All helpers below run on the scheduler thread with _cv held (the
    # stats() reader snapshots under the same lock). Entries pin their
    # blocks with _CACHE_OWNER references; sequences that match bump
    # refcounts instead of re-prefilling, and a holder that must write
    # into a shared block COW-copies it first (engine._decode_round).

    @staticmethod
    def _digest(ids, t):
        return hashlib.sha1(
            np.ascontiguousarray(ids[:t]).tobytes()).hexdigest()

    def _match_prefix(self, ids, sig=(0, 0), full_ok=True):
        """Longest cached prefix of `ids` UNDER adapter signature `sig`:
        the full-prompt entry first (total reuse — prefill skipped
        entirely), then chunk boundaries descending. Token contents are
        verified, never just hashes. `full_ok=False` (a sampled
        request) skips any entry covering the WHOLE prompt: such a hit
        would deliver the publisher's next token, but a sampled request
        must draw its own first token from the final chunk's logits."""
        plen = len(ids)
        if full_ok:
            e = self._prefix_cache.get(
                ("full", plen, self._digest(ids, plen), sig))
            if e is not None and np.array_equal(e["tokens"], ids):
                e["stamp"] = next(self._lru)
                return e
        if self._chunk:
            t = (plen // self._chunk) * self._chunk
            if not full_ok and t == plen:
                t -= self._chunk
            while t >= self._chunk:
                e = self._prefix_cache.get(
                    ("chunk", t, self._digest(ids, t), sig))
                if e is not None and np.array_equal(e["tokens"], ids[:t]):
                    e["stamp"] = next(self._lru)
                    return e
                t -= self._chunk
        return None

    def _prefix_insert(self, kind, toks, blocks, next_token, sig=(0, 0)):
        """Publish `blocks` (holding the KV of `toks`) for reuse; the
        cache takes its own reference on every block. Bounded by the
        block cap (LRU evictions make room; an oversized entry is simply
        not cached). `sig` is the publisher's `(slot, generation)`
        adapter signature: KV computed under one adapter version can
        only ever be matched under the same one."""
        key = (kind, len(toks), self._digest(toks, len(toks)), sig)
        e = self._prefix_cache.get(key)
        if e is not None:
            e["stamp"] = next(self._lru)
            return
        # the cap bounds PHYSICAL pinned blocks: entries at successive
        # chunk boundaries overlap on their shared prefix blocks, so the
        # per-entry sum would overcount quadratically and evict far
        # before the budget is actually reached
        want = set(blocks)

        def held():
            return len({b for x in self._prefix_cache.values()
                        for b in x["blocks"]} | want)

        while self._prefix_cache and held() > self._prefix_cap:
            self._evict_one()
        if held() > self._prefix_cap:
            return
        self.pool.incref(blocks, owner=_CACHE_OWNER)
        self._prefix_cache[key] = {
            "key": key, "tokens": np.array(toks, np.int32),
            "t": len(toks), "blocks": list(blocks),
            "next_token": int(next_token), "stamp": next(self._lru)}

    def _evict_one(self, keep=None):
        """Drop the least-recently-used cache entry (never `keep`) and
        release its block references. Returns the entry or None."""
        victims = [e for e in self._prefix_cache.values()
                   if e is not keep]
        if not victims:
            return None
        e = min(victims, key=lambda x: x["stamp"])
        del self._prefix_cache[e["key"]]
        self.pool.decref(e["blocks"], owner=_CACHE_OWNER)
        self._prefix_evictions += 1
        return e

    def _evict_for(self, need_free, keep=None):
        """Evict LRU entries until `need_free` blocks are free (admission
        pressure beats cached prefixes). True when satisfied."""
        while self.pool.free_count < need_free:
            if self._evict_one(keep=keep) is None:
                return False
        return True

    def _clear_prefix_cache_locked(self):
        for e in list(self._prefix_cache.values()):
            self.pool.decref(e["blocks"], owner=_CACHE_OWNER)
        self._prefix_cache.clear()

    def _push_tokens(self, seq, toks):
        """Release tokens to the sequence's stream (stop-sequence
        hold-back happens upstream in `_deliver`)."""
        for t in toks:
            seq.stream._push(int(t))
        if toks:
            with self._lock:
                self._tokens_out += len(toks)

    def _deliver(self, seq, tok):
        """Commit one decoded token: stream it out and retire the
        sequence if it just finished.

        Stop sequences are enforced here, scheduler-side: a token is
        held back while it could still be the prefix of a stop match,
        and released only once it provably is not.  The invariant —
        released tokens never end with a proper prefix of any stop
        sequence — is what makes router failover correct: the resume
        `committed` prefix regenerates the held tail bit-identically
        (counter RNG), so the stop still truncates at the same point.
        """
        seq.last_token = tok
        seq.generated += 1
        seq.out_tokens.append(int(tok))
        if seq.generated == 1:
            seq.t_first = time.perf_counter()
        if seq.generated == 1 and seq.submitted_at is not None:
            ttft = self._clock() - seq.submitted_at
            self._h_ttft.observe(ttft, ctx=seq.span.ctx)
            if self._h_ttft_shared is not None:
                # exemplar: the TTFT bucket remembers this sequence's
                # trace id (scrape -> slow-TTFT bucket -> /traces/<id>)
                self._h_ttft_shared.observe(ttft, ctx=seq.span.ctx)
            if seq.span.ctx is not None:
                _otrace.event_in("decode.first_token", seq.span.ctx,
                                 attrs={"seq": seq.id, "ttft_s": ttft})
        sps = (seq.sampling.stop_sequences
               if seq.sampling is not None else ())
        if not sps:
            self._push_tokens(seq, [tok])
        else:
            seq.held.append(int(tok))
            out = seq.out_tokens
            hit = None
            for stop in sps:
                ls = len(stop)
                if len(out) >= ls and tuple(out[-ls:]) == stop:
                    hit = stop
                    break
            if hit is not None:
                # the stop's tokens themselves are swallowed; everything
                # held before them is released
                flush = seq.held[:len(seq.held) - len(hit)]
                seq.held = []
                self._push_tokens(seq, flush)
                with self._lock:
                    self._stop_hits += 1
                self._finish(seq, "completed")
                return
            keep = 0
            for stop in sps:
                top = min(len(stop) - 1, len(seq.held))
                for l in range(top, keep, -1):
                    if tuple(out[-l:]) == stop[:l]:
                        keep = l
                        break
            if len(seq.held) > keep:
                flush = seq.held[:len(seq.held) - keep]
                seq.held = seq.held[len(seq.held) - keep:]
                self._push_tokens(seq, flush)
        if (self.eos_token_id is not None and tok == self.eos_token_id) \
                or seq.generated >= seq.max_new:
            self._finish(seq, "completed")

    def _cow_block(self, seq, bi):
        """Copy-on-write privatize `seq.blocks[bi]` before a write: one
        donated dispatch (the pool buffers are aliased in place, so this
        costs one block's traffic — `pool.copy_block`, the eager
        fallback, would re-materialize every pool tensor)."""
        new = self.pool.alloc(1, owner=seq.id)[0]
        self.pool.tensors = self._cow_fn()(
            self.pool.tensors,
            np.asarray(seq.blocks[bi], np.int32),
            np.asarray(new, np.int32))
        self.pool.decref([seq.blocks[bi]], owner=seq.id)
        seq.blocks[bi] = new
        seq.outstanding -= 1
        with self._lock:
            self._cow_copies += 1

    def _decode_round(self, phase=None):
        # step-boundary sweep: cancelled / expired sequences leave before
        # another step is spent on them
        for seq in list(self._active):
            if seq.cancelled:
                self._finish(seq, "cancelled", PoolClosed(
                    f"sequence {seq.id} cancelled mid-generation"))
            elif seq.deadline.expired():
                self._finish(seq, "timed_out", DeadlineExceeded(
                    f"sequence {seq.id} exceeded its deadline "
                    f"mid-generation"))
        active = list(self._active)
        if not active:
            return
        spec = []
        if self._spec_on:
            # a sequence speculates when (a) it still wants at least two
            # tokens (a 1-token remainder is exactly one plain step) and
            # (b) all K+1 verify rows fit the normal block table — near
            # max_length (at most the last K tokens) it falls back to
            # plain steps, keeping the verify gather width identical to
            # the decode step's (the same dense view, the same sums)
            limit = self._nb * self.block_size
            spec = [s for s in active
                    if s.max_new - s.generated > 1
                    and s.pos + self._k + 1 <= limit
                    and s.sampling is None and s.adapter is None
                    and not s.suspect]
            active = [s for s in active if s not in spec]
        if spec:
            # sequences whose draft is still catching up (one chunk per
            # round) rejoin the plain batch — generation never stalls
            # behind a long catch-up
            active += self._speculate_round(spec)
        if active and self._bd is not None:
            self._bd_round(active, phase)
        elif active:
            self._plain_round(active)

    def _grow_for_write(self, active):
        """Lazy block growth + copy-on-write for the row(s) this step
        writes at `seq.pos` (see `_plain_round`); a sequence the pool
        cannot serve fails and leaves `active`."""
        for seq in list(active):
            try:
                if seq.pos >= len(seq.blocks) * self.block_size:
                    seq.blocks += self.pool.alloc(1, owner=seq.id)
                    seq.outstanding -= 1
                else:
                    bi = seq.pos // self.block_size
                    if self.pool.refcount(seq.blocks[bi]) > 1:
                        self._cow_block(seq, bi)
            except OutOfBlocks as e:
                active.remove(seq)
                self._finish(seq, "failed", RequestFailed(
                    f"sequence {seq.id}: block pool exhausted "
                    f"mid-decode (admission reserve bug)", cause=e))

    def _plain_round(self, active):
        # lazy block growth + copy-on-write: the admission reserve
        # guarantees success of both. This step writes each sequence's
        # row at seq.pos — a write landing in a block some OTHER holder
        # (the prefix cache, or a prefix-sharing batchmate) also
        # references must not be visible to them, so the sequence copies
        # that one block first and drops its shared reference.
        names = _DECODE
        with _otrace.span(names["grow"], profile=True):
            self._grow_for_write(active)
        suspects = [s for s in active if s.suspect]
        if suspects:
            # members of a dispatch that failed after it consumed the pool
            # (`_rebuild_pools`): each alone, until a step of its own has
            # come back
            self._run_isolated(suspects)
            active = [s for s in active if not s.suspect
                      and s.state == _ACTIVE]
        if not active:
            return
        try:
            nxt = self._dispatch_decode(active)
        except PoolClosed:
            return           # engine stopping; shutdown fails leftovers
        except RequestFailed as e:
            if len(active) == 1:
                self._finish(active[0], "failed", e)
                return
            # a multi-sequence step failed: blame is ambiguous, so re-run
            # as isolated singles — only the culpable sequence fails
            with self._lock:
                self._isolations += 1
            self._run_isolated(active)
            return
        with _otrace.span(names["deliver"], profile=True):
            for seq, tok in zip(active, nxt):
                self._deliver(seq, int(tok))

    def _run_linked_step(self, name, event_name, seqs, hook_tag, info,
                         dispatch, sweep=False, member_attrs=None,
                         pool=None):
        """Shared scaffolding for every gathered multi-sequence dispatch
        (plain decode step, speculative propose, speculative verify):
        fault hook, one step-trace root span LINKING every member
        sequence's trace id with a per-member back-link event (so a
        sequence's record shows exactly which shared dispatches carried
        it), lockcheck blocking region + tpu-san hot region around the
        XLA call, optional non-finite sweep over the freshly written
        pool, and the sanctioned host fetch — one implementation, three
        steps. `dispatch(pool_tensors)` runs the compiled program, which
        consumes them (`_consume`; `pool`: the target's unless given),
        and returns `(new_pool_tensors, host_array)` (the block step: a
        tuple of arrays); `member_attrs` (one dict a sequence) joins that
        member's back-link event."""
        hook = self._fault_hook
        ids = [s.id for s in seqs]
        traced = ([s for s in seqs
                   if s.span.ctx is not None and s.span.ctx.sampled]
                  if _otrace.enabled() else [])
        member_extra = {k: v for k, v in info.items() if k != "bucket"}
        per_member = {s.id: a for s, a in zip(seqs, member_attrs or ())}
        names = _DECODE
        rnd = self._round_no
        self._last_step = (info.get("bucket"), ids)
        pool = self.pool if pool is None else pool

        def run(_member, hctx, attempt):
            if hook is not None:
                hook(hook_tag, ids, info)
            # `round` joins the step's own trace to the scheduler round
            # that submitted it
            step_span = _otrace.null_span() if not traced else \
                _otrace.root_span(
                    name,
                    attrs={**info, "n": len(seqs), "round": rnd,
                           "links": [s.span.trace_id_hex
                                     for s in traced]},
                    sampled=True,  # inherit the members' sampling: a
                    profile=True)  # dangling back-link helps nobody
            with step_span, _locks.blocking_region("decode.step_dispatch"):
                with _san.hot_region("decode.step_dispatch"), \
                        _otrace.span_in(names["enqueue"], hctx,
                                        profile=True):
                    new_pool, host = self._consume(attempt, pool, dispatch)
                if sweep:
                    self._san_sweep(new_pool)
                with _san.allow_host_sync("decode.token_fetch"), \
                        _otrace.span_in(names["fetch"], hctx,
                                        profile=True):
                    out = new_pool, (
                        tuple(np.asarray(a) for a in host)
                        if isinstance(host, tuple) else np.asarray(host))
            for s in traced:
                _otrace.event_in(
                    event_name, s.span.ctx,
                    attrs={"seq": s.id, "pos": int(s.pos), **member_extra,
                           **per_member.get(s.id, {}),
                           "step_trace": step_span.trace_id_hex})
            return out

        return self._submit_step(run, names, seqs)

    def _layer_attrs(self):
        """What a step's or a chunk's span says of the model's layers
        beyond full attention."""
        return {k: v for k, v in (("recurrent_layers", self._recurrent),
                                  ("latent_layers", self._latent)) if v}

    def _count_experts(self, counts, chosen, positions, chunk=False):
        """Take one dispatch's expert counts `[layers, experts held]` into
        the counters (caller holds the lock): `chosen` experts were chosen
        in all (live positions x experts a token x layers), the counts hold
        those that fell on experts held here, and `positions` (padding
        included) is what the layer's schedule chose its pass from; `chunk`
        marks a prompt chunk, whose distinct experts are also kept apart
        from the decode steps'."""
        from ...models.moe import experts_read

        if self._moe_tokens is None:
            self._moe_tokens = np.zeros(counts.shape, np.int64)
        self._moe_tokens += counts
        self._moe_choices += chosen
        distinct = int((counts > 0).sum())
        self._moe_distinct += distinct
        self._moe_chunk_distinct += distinct if chunk else 0
        self._moe_reads += counts.shape[0] * experts_read(
            positions, self.model.cfg.num_experts_per_tok, counts.shape[1])
        mean = counts.sum(axis=1) / counts.shape[1]
        self._moe_load_sum += float(
            (counts.max(axis=1) / np.maximum(mean, 1e-30)).sum())
        self._moe_load_n += counts.shape[0]

    def _take_expert_counts(self, out, tokens, live, positions,
                            chunk=False):
        """Split what a step or a prompt `chunk` of a model with expert
        layers brought back, `tokens` token ids and then the layers'
        counts, and count; the token ids. `live` of the dispatch's
        `positions` were real."""
        if not self._moe_layers:
            return out
        counts = out[tokens:].reshape(self._moe_layers, -1)
        with self._lock:
            self._count_experts(
                counts, live * self._moe_layers
                * self.model.cfg.num_experts_per_tok, positions, chunk)
        return out[:tokens]

    def _dispatch_decode(self, active):
        n = len(active)
        bucket = next(b for b in self.decode_buckets if b >= n)
        with _otrace.span(_DECODE["pack"], profile=True):
            fn = self._decode_fn(bucket)
            pv, bv = self._weights()
            ats = self._adapter_stacks()
            tokens = np.zeros(bucket, np.int32)
            positions = np.zeros(bucket, np.int32)
            tables = np.zeros((bucket, self._nb), np.int32)  # pad -> 0
            aids = np.zeros(bucket, np.int32)  # pad rows -> slot 0 (no-op)
            slots = np.zeros(bucket, np.int32)  # pad rows -> state slot 0
            for i, seq in enumerate(active):
                tokens[i] = seq.last_token
                positions[i] = seq.pos
                tables[i] = self._padded_table(seq)
                aids[i] = seq.adapter_slot
                slots[i] = seq.slot
            hist = self._hist_pack(active, bucket)
            samp = self._samp_pack(active, bucket)
            extra = (slots,) if self._recurrent else ()
        new_pool, nxt = self._run_linked_step(
            "decode.step", "decode.step_join", active, "decode",
            {"bucket": bucket, **self._layer_attrs()},
            lambda pool_ts: fn(pv, bv, ats, pool_ts, tokens, positions,
                               tables, aids, hist, samp, *extra),
            sweep=True)
        nxt = self._take_expert_counts(nxt, bucket, n, bucket)
        self.pool.tensors = new_pool
        for seq in active:
            seq.pos += 1
        with self._lock:
            self._steps_run += 1
            self._step_slots += bucket
            self._step_active += n
            if self._recurrent:
                self._lin_step_tokens += n
        return nxt[:n]

    # -- block diffusion round ---------------------------------------------
    # One dispatch a round for the whole active batch: every sequence
    # forwards its open block once, in its own phase. What the host keeps
    # of a block (`seq.bd`) changes only in `_bd_advance`, after the
    # dispatch has returned: a wedged step that is re-submitted, and a
    # failed step re-run as isolated singles, start from the same state.

    def _bd_open_block(self, seq, fixed=()):
        """The next block of `seq`, all mask tokens but for `fixed` (the
        prompt's remainder, at the head of the first block)."""
        bl, mask = self._bd["block_length"], self._bd["mask_token_id"]
        tokens = np.full(bl, mask, np.int32)
        tokens[:len(fixed)] = fixed
        seq.bd = {"tokens": tokens,
                  "masked": np.arange(bl) >= len(fixed),
                  "passes": np.zeros(bl, np.int32),  # pass that fixed it
                  "t": 0,                   # denoising passes it has had
                  "given": len(fixed),      # leading positions not generated
                  "index": 0 if seq.bd is None else seq.bd["index"] + 1}

    def _bd_round(self, active, phase=None):
        names = _DECODE
        with _otrace.span(names["grow"], profile=True):
            # the block's rows are written by its commit pass only, into
            # ONE pool block (block_length divides block_size): the same
            # grow / copy-on-write rule as a plain step's row at seq.pos
            self._grow_for_write(active)
        if not active:
            return
        commits = sum(not s.bd["masked"].any() for s in active)
        if phase is not None:
            phase.set_attr("denoise", len(active) - commits)
            phase.set_attr("commit", commits)
        suspects = [s for s in active if s.suspect]
        if suspects:                    # as `_plain_round`'s
            self._bd_isolated(suspects)
            active = [s for s in active if not s.suspect
                      and s.state == _ACTIVE]
            if not active:
                return
        try:
            out = self._bd_dispatch(active)
        except PoolClosed:
            return           # engine stopping; shutdown fails leftovers
        except RequestFailed as e:
            if len(active) == 1:
                self._finish(active[0], "failed", e)
                return
            with self._lock:
                self._isolations += 1
            self._bd_isolated(active)
            return
        with _otrace.span(names["deliver"], profile=True):
            for i, seq in enumerate(active):
                self._bd_advance(seq, *(row[i] for row in out))

    def _bd_isolated(self, seqs):
        """`_run_isolated` for block steps: one dispatch a sequence."""
        for seq in list(seqs):
            if seq.state != _ACTIVE:
                continue
            try:
                out = self._bd_dispatch([seq])
            except PoolClosed:
                return
            except RequestFailed as e:
                self._finish(seq, "failed", e)
                continue
            seq.suspect = False
            self._bd_advance(seq, *(row[0] for row in out))

    def _bd_dispatch(self, active):
        """One block forward a sequence. Returns per sequence (whether it
        was a commit pass, the arg-max tokens [B], their confidences
        [B])."""
        n = len(active)
        bl = self._bd["block_length"]
        bucket = next(b for b in self.decode_buckets if b >= n)
        with _otrace.span(_DECODE["pack"], profile=True):
            fn = self._bd_fn(bucket)
            pv, bv = self._weights()
            tokens = np.full((bucket, bl), self.pad_token_id, np.int32)
            positions = np.zeros(bucket, np.int32)
            tables = np.zeros((bucket, self._nb), np.int32)  # pad -> 0
            commit = np.zeros(bucket, np.int32)
            valid = np.zeros(bucket, np.int32)
            valid[:n] = 1
            for i, seq in enumerate(active):
                tokens[i] = seq.bd["tokens"]
                positions[i] = seq.pos
                tables[i] = self._padded_table(seq)
                commit[i] = not seq.bd["masked"].any()
        ncommit = int(commit.sum())
        member = [{"block": s.bd["index"], "pass": 0 if c else s.bd["t"] + 1}
                  for s, c in zip(active, commit)]
        new_pool, (best, conf, counts) = self._run_linked_step(
            "decode.step", "decode.step_join", active, "decode",
            {"bucket": bucket, "denoise": n - ncommit, "commit": ncommit,
             "block": [m["block"] for m in member],
             "pass": [m["pass"] for m in member]},
            lambda pool_ts: fn(pv, bv, pool_ts, tokens, positions, tables,
                               commit, valid),
            sweep=True, member_attrs=member)
        self.pool.tensors = new_pool
        with self._lock:
            self._steps_run += 1
            self._step_slots += bucket
            self._step_active += n
            self._bd_forwards += n
            self._bd_commit_forwards += ncommit
            self._bd_head_dispatches += ncommit < n
            self._bd_context_tokens += int(positions[:n].sum()) + n * bl
            if counts.size:
                self._count_experts(
                    counts, n * bl * counts.shape[0]
                    * self.model.cfg.num_experts_per_tok, bucket * bl)
        return commit[:n].astype(bool), best[:n], conf[:n]

    def _bd_advance(self, seq, committed, best, conf):
        """Take one returned forward into the sequence's block state: a
        denoising pass fixes the block_length / denoising_steps masked
        positions of highest confidence (`low_confidence_static`; ties to
        the lower position, a stable sort); a commit pass has written the
        block's cache rows, so its generated tokens go to the stream, each
        with the pass that fixed it, and the next block opens."""
        st, bl = seq.bd, self._bd["block_length"]
        if not committed:
            st["t"] += 1
            cand = np.flatnonzero(st["masked"])
            order = np.argsort(-conf[cand].astype(np.float32),
                               kind="stable")
            fix = cand[order[:bl // self._bd["denoising_steps"]]]
            st["tokens"][fix] = best[fix]
            st["masked"][fix] = False
            st["passes"][fix] = st["t"]
            with self._lock:
                self._bd_tokens_fixed += len(fix)
            return
        seq.pos += bl
        with self._lock:
            self._bd_blocks_committed += 1
        self._bd_open_block(seq)
        for tok, t in zip(st["tokens"][st["given"]:],
                          st["passes"][st["given"]:]):
            seq.stream.passes.append(int(t))
            self._deliver(seq, int(tok))
            if seq.state == _DONE:      # max_new or EOS inside the block:
                break                   # its tail is not delivered

    def _run_isolated(self, seqs):
        for seq in list(seqs):
            if seq.state != _ACTIVE:
                continue
            try:
                nxt = self._dispatch_decode([seq])
            except PoolClosed:
                return
            except RequestFailed as e:
                self._finish(seq, "failed", e)
                continue
            seq.suspect = False
            self._deliver(seq, int(nxt[0]))

    # -- speculative decoding round ----------------------------------------
    # One round per scheduler iteration for every eligible sequence:
    #   1. draft catch-up   — (re)build the draft's KV over committed
    #                         tokens where it lags (first round, prefix-
    #                         cache full hit, post-fallback)
    #   2. propose          — ONE draft dispatch: K autoregressive tokens
    #                         per sequence into the draft pool
    #   3. verify           — ONE target dispatch: K+1 positions scored
    #                         per sequence (the per-position equations
    #                         the plain decode step batches)
    #   4. commit/rollback  — greedy acceptance: longest draft prefix
    #                         matching the target argmax + the target's
    #                         correction/bonus token committed; rejected
    #                         positions roll back POSITIONALLY (both
    #                         pools' rows past the committed position are
    #                         rewritten before they can ever be attended)
    # A failed shared propose/verify dispatch falls back to plain
    # isolated decode from committed state — survivors lose nothing (their
    # tokens the batched step's within the module's determinism contract)
    # and no uncommitted token is ever delivered.

    def _committed_tokens(self, seq):
        """Every committed token (prompt + generated), index == cache
        position; length is seq.pos + 1 with seq.last_token at the end.
        Uses `out_tokens`, not the stream: tokens held back by a pending
        stop-sequence match are committed (they occupy cache positions)
        even though they have not been released to the caller."""
        if not seq.out_tokens:
            return seq.prompt
        return np.concatenate(
            [seq.prompt, np.asarray(seq.out_tokens, np.int32)])

    def _draft_catchup(self, seq):
        """Bring the draft's KV toward the committed position: prefill
        committed tokens [draft_pos, pos) through the draft prefill
        executables, chunked at block-aligned starts so the block-wise
        scatter stays exact. Dispatches at most ONE chunk per call (=
        per scheduler round — the same one-chunk-per-round scheduling
        chunked prefill uses, so a long catch-up cannot head-of-line
        block the running batch); returns True when the draft is fully
        caught up. A still-lagging sequence plain-decodes this round
        (one token) while catch-up gains a whole chunk per round, so the
        gap closes whenever the largest block-aligned bucket exceeds
        the block size plus one; the normal case (the prompt, a full
        hit, a short post-fallback tail) catches up in one chunk.
        """
        if seq.draft_pos >= seq.pos:
            return True
        committed = self._committed_tokens(seq)
        aligned = [b for b in self.prefill_buckets
                   if b % self.block_size == 0]
        # the prefill scatter writes block-wise from the chunk's
        # start block at in-block offset 0, so the chunk start MUST
        # be block-aligned. draft_pos is unaligned after a
        # speculative fallback advanced the sequence without the
        # draft (it froze at the last commit): round DOWN and
        # re-feed the partial block's committed tokens — recomputing
        # their (identical) rows is always correct, a shifted
        # scatter would silently corrupt the draft's KV
        start = (seq.draft_pos // self.block_size) * self.block_size
        remaining = seq.pos - start
        if remaining > self.prefill_buckets[-1]:
            if not aligned:
                raise RequestFailed(
                    f"sequence {seq.id}: draft catch-up of "
                    f"{remaining} tokens needs a block-aligned "
                    f"prefill bucket (have {self.prefill_buckets})")
            this_len = aligned[-1]
        else:
            this_len = remaining
        pbucket = next(p for p in self.prefill_buckets
                       if p >= this_len)
        need = self.draft_pool.blocks_for(start + this_len) \
            - len(seq.draft_blocks)
        if need > 0:
            try:
                seq.draft_blocks += self.draft_pool.alloc(
                    need, owner=seq.id)
                seq.draft_outstanding -= need
            except OutOfBlocks as e:
                raise RequestFailed(
                    f"sequence {seq.id}: draft pool exhausted at "
                    f"catch-up (admission reserve bug)",
                    cause=e) from e
        fn = self._draft_prefill_fn(pbucket)
        pv, bv = self._d_weights()
        tokens = np.full((1, pbucket), self.pad_token_id, np.int32)
        tokens[0, :this_len] = committed[start:start + this_len]
        table = np.zeros(self._nb + self._prefill_tail, np.int32)
        table[: len(seq.draft_blocks)] = seq.draft_blocks
        hook = self._fault_hook
        sctx = seq.span.ctx

        names = _DECODE

        def run(_member, hctx, attempt):
            if hook is not None:
                hook("draft_prefill", [seq.id],
                     {"bucket": pbucket, "start": start,
                      "tokens": this_len})
            with _otrace.span_in(
                    "decode.draft_catchup", sctx,
                    attrs=None if sctx is None else
                    {"seq": seq.id, "bucket": pbucket,
                     "start": start, "tokens": this_len}), \
                    _locks.blocking_region("decode.step_dispatch"):
                with _san.hot_region("decode.step_dispatch"), \
                        _otrace.span_in(names["enqueue"], hctx,
                                        profile=True):
                    new_pool, nxt = self._consume(
                        attempt, self.draft_pool, lambda pool_ts: fn(
                            pv, bv, pool_ts, tokens,
                            np.asarray(start, np.int32),
                            np.asarray(this_len, np.int32), table))
                # the argmax is discarded (the propose dispatch
                # starts from last_token) — fetched only to fence
                # the dispatch for the pool's hang detection
                with _san.allow_host_sync("decode.token_fetch"), \
                        _otrace.span_in(names["fetch"], hctx,
                                        profile=True):
                    int(np.asarray(nxt))
                return new_pool

        self.draft_pool.tensors = self._submit_step(run, names, [seq])
        seq.draft_pos = start + this_len
        with self._lock:
            self._spec_catchup_chunks += 1
            self._spec_draft_dispatches += 1
        return seq.draft_pos >= seq.pos

    def _prepare_spec_blocks(self, seq):
        """Block growth + COW for one speculation round. Target rows
        `pos .. pos+K` are written this round, but only rows below
        `plen + max_new` can ever be committed — those get real blocks
        (within the sequence's existing worst-case reservation); rows
        past that sink into reserved block 0 through table padding, and
        their garbage can only influence logits at positions that are
        themselves uncommittable. Only the block holding `pos` can be
        shared (shared blocks never extend past the prompt), so the COW
        rule is unchanged from the plain path."""
        plen = len(seq.prompt)
        cap_rows = min(seq.pos + self._k + 1, plen + seq.max_new)
        need = self.pool.blocks_for(cap_rows) - len(seq.blocks)
        if need > 0:
            seq.blocks += self.pool.alloc(need, owner=seq.id)
            seq.outstanding -= need
        bi = seq.pos // self.block_size
        if bi < len(seq.blocks) \
                and self.pool.refcount(seq.blocks[bi]) > 1:
            self._cow_block(seq, bi)
        # the propose scan writes K+1 draft rows (pos .. pos+K — the
        # last keeps the draft valid through a bonus round)
        dneed = self.draft_pool.blocks_for(seq.pos + self._k + 1) \
            - len(seq.draft_blocks)
        if dneed > 0:
            seq.draft_blocks += self.draft_pool.alloc(dneed, owner=seq.id)
            seq.draft_outstanding -= dneed

    def _dispatch_propose(self, seqs):
        n = len(seqs)
        bucket = next(b for b in self.decode_buckets if b >= n)
        fn = self._propose_fn(bucket)
        pv, bv = self._d_weights()
        tokens = np.zeros(bucket, np.int32)
        positions = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, self._nb), np.int32)  # pad rows -> 0
        for i, seq in enumerate(seqs):
            tokens[i] = seq.last_token
            positions[i] = seq.pos
            tables[i, : len(seq.draft_blocks)] = seq.draft_blocks
        new_pool, props = self._run_linked_step(
            "decode.speculate", "decode.speculate", seqs, "speculate",
            {"bucket": bucket, "k": self._k},
            lambda pool_ts: fn(pv, bv, pool_ts, tokens, positions, tables),
            pool=self.draft_pool)
        self.draft_pool.tensors = new_pool
        with self._lock:
            self._spec_draft_dispatches += 1
        return props[:n]

    def _dispatch_verify(self, seqs, props):
        n = len(seqs)
        bucket = next(b for b in self.decode_buckets if b >= n)
        fn = self._verify_fn(bucket)
        pv, bv = self._weights()
        tokens = np.zeros((bucket, self._k + 1), np.int32)
        positions = np.zeros(bucket, np.int32)
        tables = np.zeros((bucket, self._nb), np.int32)  # pad rows -> 0
        for i, seq in enumerate(seqs):
            tokens[i, 0] = seq.last_token
            tokens[i, 1:] = props[i]
            positions[i] = seq.pos
            tables[i] = self._padded_table(seq)
        new_pool, preds = self._run_linked_step(
            "decode.verify", "decode.verify", seqs, "verify",
            {"bucket": bucket, "k": self._k},
            lambda pool_ts: fn(pv, bv, pool_ts, tokens, positions, tables),
            sweep=True)
        self.pool.tensors = new_pool
        with self._lock:
            self._spec_verify_dispatches += 1
        return preds[:n]

    def _speculate_round(self, seqs):
        """One speculation round; returns the sequences DEFERRED to the
        plain round because their draft is still catching up (at most
        one catch-up chunk dispatches per sequence per round)."""
        ready, deferred = [], []
        for seq in seqs:
            try:
                caught_up = self._draft_catchup(seq)
            except PoolClosed:
                return deferred
            except RequestFailed as e:
                self._finish(seq, "failed", e)
                continue
            except _PoolRebuilt:
                raise
            except Exception as exc:  # noqa: BLE001 — e.g. an XLA
                # compile failure: fail THIS sequence, not the scheduler
                self._finish(seq, "failed", RequestFailed(
                    f"sequence {seq.id}: draft catch-up error: "
                    f"{type(exc).__name__}: {exc}", cause=exc))
                continue
            if not caught_up:
                deferred.append(seq)
                continue
            try:
                self._prepare_spec_blocks(seq)
            except OutOfBlocks as e:
                self._finish(seq, "failed", RequestFailed(
                    f"sequence {seq.id}: block pool exhausted preparing "
                    f"a speculation round (admission reserve bug)",
                    cause=e))
                continue
            ready.append(seq)
        if not ready:
            return deferred
        try:
            props = self._dispatch_propose(ready)
            preds = self._dispatch_verify(ready, props)
        except PoolClosed:
            return deferred  # engine stopping; shutdown fails leftovers
        except RequestFailed:
            # blame is ambiguous in a shared speculative dispatch (and
            # the fault may be speculation-specific): fall back to plain
            # ISOLATED decode from the committed state. No uncommitted
            # token was delivered, the draft rolls back positionally
            # (draft_pos is untouched), and survivors lose nothing —
            # a genuinely-poisoned sequence then fails alone in its own
            # single-sequence dispatch.
            with self._lock:
                self._spec_fallbacks += 1
                if len(ready) > 1:
                    self._isolations += 1
            self._run_isolated(ready)
            return deferred
        with self._lock:
            self._spec_rounds += 1
        self._commit_speculation(ready, props, preds)
        return deferred

    def _commit_speculation(self, seqs, props, preds):
        """Greedy acceptance + commit: token i+1 is committed iff the
        draft's proposal equals the target's argmax at position pos+i —
        and what is COMMITTED is always the target's argmax, so the
        output token sequence is exactly the plain greedy one."""
        k = self._k
        for i, seq in enumerate(seqs):
            d = [int(x) for x in props[i]]
            g = [int(x) for x in preds[i]]
            a = 0
            while a < k and d[a] == g[a]:
                a += 1
            commit = d[:a] + [g[a]]     # accepted + correction/bonus
            pos0 = seq.pos
            delivered = 0
            for tok in commit:
                self._deliver(seq, tok)
                delivered += 1
                if seq.state == _DONE:   # EOS or max_new: stop HERE —
                    break                # nothing uncommittable leaks out
            seq.pos = pos0 + delivered
            # rollback line: rows >= draft_pos are treated invalid and
            # rewritten before the draft can ever attend them. Valid
            # draft rows after this round: pos0 + min(delivered, K+1)
            # — the propose scan wrote rows pos0..pos0+K (the K+1th
            # keeps a bonus round fully covered), each valid iff its
            # token was committed, which delivered <= K+1 guarantees
            seq.draft_pos = seq.pos
            # acceptance is a DRAFT-QUALITY measure: `a` proposals agreed
            # with the target, `k - a` disagreed (rejected). A proposal
            # the target agreed with but EOS/max_new truncated out of
            # delivery is NOT a rejection — counting it as one would
            # read a perfect draft as < 1.0 acceptance on every
            # truncated tail
            seq.spec_proposed += k
            seq.spec_accepted += a
            if seq.span.ctx is not None:
                _otrace.event_in(
                    "decode.spec_commit", seq.span.ctx,
                    attrs={"seq": seq.id, "accepted": a,
                           "rejected": k - a,
                           "committed": delivered})
            with self._lock:
                # proposed is counted HERE, not at propose-dispatch time:
                # a fallback round's proposals are never judged, and
                # counting them would break proposed == accepted +
                # rejected and read a fault as a draft-quality dip
                self._spec_proposed += k
                self._spec_accepted += a
                self._spec_rejected += k - a
                self._spec_committed += delivered
                if delivered == k + 1:
                    self._spec_bonus += 1

    # -- lifecycle ---------------------------------------------------------
    def _finish(self, seq, status, error=None):
        with self._cv:
            self._finish_locked(seq, status, error)

    def _finish_locked(self, seq, status, error=None):
        if seq.state == _DONE:
            return
        seq.state = _DONE
        seq.outstanding = 0
        if seq in self._active:
            self._active.remove(seq)
        if seq in self._prefill_q:
            self._prefill_q.remove(seq)
        if seq.held and status == "completed":
            # eos/max_new ended the stream mid-hold: no stop match is
            # coming, so the held tail is plain output — release it.
            # Non-completed finishes deliberately DROP the held tail:
            # a failover resume regenerates it bit-identically (counter
            # RNG), and the released prefix keeps the no-stop-prefix
            # invariant the resume-side stop scan depends on.
            # inline push: we already hold `_lock` (via `_cv`) here and
            # `_push_tokens` would re-take the non-reentrant lock
            for t in seq.held:
                seq.stream._push(int(t))
            self._tokens_out += len(seq.held)
        seq.held = []
        # drops every reference this sequence holds: exclusive blocks
        # free, shared prefix blocks stay for their other holders
        self.pool.free_owned(seq.id)
        if self._recurrent:
            self.pool.free_slot(seq.id)
        if self._adapters is not None:
            self._adapters.release_owned(seq.id)
        if self._spec_on:
            self.draft_pool.free_owned(seq.id)
            seq.draft_outstanding = 0
        if status == "completed":
            self._completed += 1
        elif status == "failed":
            self._failed += 1
        elif status == "timed_out":
            self._timed_out += 1
        else:
            self._cancelled += 1
        # close the sequence's root span with its terminal status; a
        # typed failure additionally pins the trace as a postmortem.
        # Its duration decomposes into the three stretches below (a
        # stretch the sequence never reached is 0)
        if seq.span.ctx is not None:
            now = time.perf_counter()
            admit = seq.t_admit if seq.t_admit is not None else now
            first = seq.t_first if seq.t_first is not None else now
            for key, value in (("queue_wait_s", admit - seq.t_submit),
                               ("prefill_s", first - admit),
                               ("decode_s", now - first),
                               ("chunks", seq.chunks),
                               ("round_admitted", seq.round_admit),
                               ("round_finished", self._round_no)):
                seq.span.set_attr(key, value)
        if error is not None:
            _otrace.pin_failure(seq.span.ctx, error)
        seq.span.end(error=error if status != "completed" else None,
                     status="ok" if status == "completed" else status)
        seq.stream._finish(status, error)

    def shutdown(self, drain_timeout=30.0):
        """Graceful drain, mirroring `ServingPool.shutdown`: stop
        admissions, keep decoding until every live sequence finishes (or
        `drain_timeout` passes), then fail leftovers with `PoolClosed`
        and stop the scheduler + step pool. Returns True on a full
        drain. Idempotent."""
        with self._cv:
            if self._shutdown_called:
                return self._drained
            self._shutdown_called = True
            self._closed = True
            self._cv.notify_all()
        dl = Deadline(drain_timeout, clock=self._clock)
        drained = True
        while True:
            with self._cv:
                if not self._waiting and not self._active \
                        and not self._prefill_q:
                    break
            if dl.expired():
                drained = False
                break
            time.sleep(0.005)
        with self._cv:
            self._stopping = True
            self._cv.notify_all()
        self._steps.shutdown(drain_timeout=1.0)
        self._thread.join(timeout=5.0)
        with self._cv:
            leftovers = (self._waiting + list(self._prefill_q)
                         + list(self._active))
            self._waiting = []
            for seq in leftovers:
                self._finish_locked(seq, "cancelled", PoolClosed(
                    f"engine shut down before sequence {seq.id} finished"))
            # release the prefix cache's block references: a shut-down
            # engine returns the pool to allocated == 0 (the conservation
            # bar the fault injector holds every phase to)
            self._clear_prefix_cache_locked()
        if self._metrics is not None:
            self._metrics.unregister_collector(f"decode.{self.name}",
                                               self.stats)
        self._drained = drained
        return drained

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.shutdown()
        return False

    # -- observability -----------------------------------------------------
    def stats(self):
        """Counter snapshot. Conservation law (quiesced engine):
        ``admitted == completed + failed + timed_out + cancelled``; at
        any instant the right side also includes waiting + active."""
        with self._cv:
            used_tokens = sum(s.pos for s in self._active)
            alloc_slots = sum(len(s.blocks) for s in self._active) \
                * self.block_size
            lookups = self._prefix_hits + self._prefix_misses
            snap = {
                "admitted": self._admitted,
                "completed": self._completed,
                "failed": self._failed,
                "timed_out": self._timed_out,
                "cancelled": self._cancelled,
                "shed": self._shed,
                "resumed": self._resumed,
                "waiting": len(self._waiting),
                "prefilling": len(self._prefill_q),
                "active": len(self._active),
                # most sequences ever resident (prefilling + decoding)
                # at once: what admission headroom actually buys
                "peak_resident": self._peak_resident,
                "steps": self._steps_run,
                "prefills": self._prefills,
                "prefill_chunks": self._prefill_chunks,
                "tokens_out": self._tokens_out,
                "wedged_steps": self._wedged_steps,
                "isolation_rounds": self._isolations,
                # compiled calls that consumed a pool (steps, chunks,
                # verify, propose, draft catch-up) and came back: steps +
                # prefill_chunks in a sound run without speculation
                "donated_dispatches": self._donated_dispatches,
                # fault contract (c): the cache built anew after a
                # dispatch that consumed it failed
                "pool_rebuilds": self._pool_rebuilds,
                "occupancy": (self._step_active / self._step_slots)
                if self._step_slots else 0.0,
                # the two cumulative counters behind `occupancy`: a
                # reader differences them over its own window
                "step_active": self._step_active,
                "step_slots": self._step_slots,
                "rounds": self._round_no,
                "slow_rounds": self._slow_rounds,
                "internal_fragmentation": (1.0 - used_tokens / alloc_slots)
                if alloc_slots else 0.0,
                "prefix_hit_rate": (self._prefix_hits / lookups)
                if lookups else 0.0,
                "cow_copies": self._cow_copies,
                "sampled": self._sampled,
                "stop_hits": self._stop_hits,
                "prefix_cache": {
                    "enabled": self._prefix_on,
                    "entries": len(self._prefix_cache),
                    "blocks": sum(len(e["blocks"])
                                  for e in self._prefix_cache.values()),
                    # distinct pool blocks the cache pins (entries may
                    # share blocks): a quiesced engine holds exactly
                    # these — anything beyond is a leak
                    "physical_blocks": len(
                        {b for e in self._prefix_cache.values()
                         for b in e["blocks"]}),
                    "block_cap": self._prefix_cap,
                    "hits": self._prefix_hits,
                    "full_hits": self._prefix_full_hits,
                    "misses": self._prefix_misses,
                    "tokens_reused": self._prefix_tokens_reused,
                    "evictions": self._prefix_evictions,
                },
                "compiles": {"built": self._compiled,
                             "disk": self._disk_loaded},
                "buckets": {"decode": list(self.decode_buckets),
                            "prefill": list(self.prefill_buckets),
                            "prefill_chunk": self._chunk},
                "speculative": {
                    "enabled": self._spec_on,
                    "k": self._k if self._spec_on else 0,
                    "rounds": self._spec_rounds,
                    "proposed": self._spec_proposed,
                    "accepted": self._spec_accepted,
                    # proposals the TARGET disagreed with (their draft
                    # KV rows roll back positionally; truncation-
                    # discarded agreements are not rejections)
                    "rejected": self._spec_rejected,
                    "bonus": self._spec_bonus,
                    "committed": self._spec_committed,
                    "verify_dispatches": self._spec_verify_dispatches,
                    "draft_dispatches": self._spec_draft_dispatches,
                    "catchup_chunks": self._spec_catchup_chunks,
                    "fallbacks": self._spec_fallbacks,
                    "acceptance_rate":
                        (self._spec_accepted / self._spec_proposed)
                        if self._spec_proposed else 0.0,
                    "accepted_per_dispatch":
                        (self._spec_committed
                         / self._spec_verify_dispatches)
                        if self._spec_verify_dispatches else 0.0,
                },
            }
            if self._bd is not None:
                snap["block_diffusion"] = dict(self._bd)
                snap.update(
                    bd_forwards=self._bd_forwards,
                    bd_commit_forwards=self._bd_commit_forwards,
                    bd_tokens_fixed=self._bd_tokens_fixed,
                    bd_blocks_committed=self._bd_blocks_committed,
                    bd_head_dispatches=self._bd_head_dispatches,
                    bd_context_tokens=self._bd_context_tokens)
            if self._moe_tokens is not None:
                # the expert layers, under block diffusion or not: what was
                # chosen in all, and what of it fell on the experts held
                snap.update(
                    moe_experts_held=self._moe_tokens.shape[1],
                    moe_choices_total=self._moe_choices,
                    moe_choices_local=int(self._moe_tokens.sum()),
                    moe_expert_tokens=self._moe_tokens.tolist(),
                    moe_distinct_experts=self._moe_distinct,
                    moe_chunk_distinct_experts=self._moe_chunk_distinct,
                    moe_expert_reads=self._moe_reads,
                    moe_load_max_over_mean_sum=self._moe_load_sum,
                    moe_layer_dispatches=self._moe_load_n)
            if self._recurrent:
                snap.update(
                    lin_layers=self._recurrent,
                    lin_chunk_tokens=self._lin_chunk_tokens,
                    lin_step_tokens=self._lin_step_tokens)
        th = self._h_ttft.snapshot()
        snap["ttft"] = {"count": th["count"], "avg_s": th["avg"],
                        "p50_s": th["p50"], "p99_s": th["p99"]}
        snap["blocks"] = blocks = self.pool.stats()
        if self._recurrent:
            # the two kinds of cache side by side: state slots held (and
            # their bytes) and KV blocks held
            snap.update(
                lin_state_slots=blocks["state_slots"],
                lin_state_slots_peak=blocks["state_slots_peak"],
                lin_state_bytes=blocks["state_slots"]
                * blocks["state_slot_bytes"],
                kv_blocks_in_use=blocks["allocated"])
        if self._latent:
            # token rows the allocated blocks hold, and what one of them
            # weighs over all latent-attention layers
            snap.update(
                mla_rows_in_use=blocks["allocated"] * self.block_size,
                mla_row_bytes=self._latent_row_bytes)
        if self._adapters is not None:
            snap["adapters"] = self._adapters.stats()
        if self._spec_on:
            snap["draft_blocks"] = self.draft_pool.stats()
        snap["step_pool"] = self._steps.stats()
        if self.mesh is not None:
            from ... import sharding as _shardlib

            snap["sharding"] = _shardlib.mesh_stats(
                self.mesh, {n: sh.spec
                            for n, sh in self._param_sh.items()})
        return snap
