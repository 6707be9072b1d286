"""Fault-injection harness for the resilient serving runtime.

The serving twin of tools/ckpt_fault_injector.py: where that harness kills
a checkpoint saver at every commit-protocol phase and proves atomicity,
this one injects member faults into a live `ServingPool`
(paddle_tpu/inference/serving.py) over a REAL exported model and proves
the resilience invariant for every fault phase:

  1. the pool converges back to FULL healthy capacity (every slot alive,
     every breaker closed, queue empty, nothing in flight — no stuck
     leases) once the fault stops;
  2. every admitted request either completes with bit-correct outputs or
     fails with one of the documented typed errors (`DeadlineExceeded` /
     `Overloaded` / `RequestFailed`) — never an untyped error, never a
     hang;
  3. the stats conservation law holds:
     admitted == completed + failed + timed_out + cancelled.

Phases (injected via the pool's `fault_hook`, which runs on the member's
worker thread right before execution — the in-process equivalent of the
member crashing/wedging under a request):

  crash    every 4th request raises transiently on WHICHEVER member runs
           it first (fault → quarantine + re-clone + jittered retry;
           slot-agnostic so the injection count never depends on the
           worker-scheduling lottery);
  hang     every 6th request wedges its member past the deadline (→ the
           supervisor retires the worker and restores capacity with a
           fresh clone);
  poison   one slot fails EVERY request until its circuit breaker trips
           (K consecutive failures → open), then the fault is lifted and
           the half-open probe must close the breaker again;
  corrupt  the fault scribbles garbage into the member's input handles
           before raising — quarantine must reset/replace the handles so
           no later request can silently consume them;
  none     fault-free control.

Batched phases (`batch-*`) run the same invariants with DYNAMIC BATCHING
on (ServingPool(batching=BatchConfig(...)) — bucketed AOT dispatch,
split-on-failure; see docs/serving.md):

  batch-crash   a transient fault fails a whole formed batch: it must be
                retried as split singles and every request must still
                complete bit-correct (no innocent batchmate lost);
  batch-hang    a wedged batch is failed whole by the supervisor (typed
                DeadlineExceeded for every batchmate) and capacity is
                restored with a fresh clone;
  batch-poison  ONE request deterministically raises inside its batch:
                after the split, the poison request must be the ONLY
                typed failure in its batch — every batchmate completes
                bit-correct.

Decode phases (`decode-*`) run the continuous-batching LLM engine
(paddle_tpu/inference/decode) with mixed-length generations and prove the
iteration-level invariants: BLOCK-POOL CONSERVATION (allocated + free +
reserved == total, a drained engine returns to allocated == 0 — no fault
path may leak a KV block) and SEQUENCE ISOLATION (a faulted sequence is
the only casualty; every batchmate's tokens stay bit-identical to a
fault-free solo run):

  decode-kill    cancel one sequence mid-generation (its blocks return to
                 the pool at the next step boundary);
  decode-wedge   wedge one shared decode step past the step deadline (the
                 internal step pool's EXISTING hang detection retires the
                 wedged worker; the engine re-dispatches the pure step and
                 nobody loses a token);
  decode-poison  deterministically fail ONE sequence's prefill (poisoned
                 feed) — typed RequestFailed for it alone;
  decode-none    fault-free control (also produces the per-prompt solo
                 reference tokens the other phases compare against);
  decode-spec    SPECULATIVE decoding (draft-proposed, one-dispatch
                 verified) under faults: one shared verify dispatch is
                 poisoned mid-round (the engine falls back to plain
                 isolated decode — no uncommitted token leaks) and one
                 sequence is cancelled mid-generation. Survivors must be
                 BIT-EXACT vs the non-speculative references, draft AND
                 target block pools must conserve, and the whole phase
                 runs with zero post-warmup retraces (tpu-san);
  decode-cow     N sequences share a cached prompt prefix (refcounted
                 blocks, one physical copy; chunked prefill); one is
                 cancelled mid-decode. Refcount conservation must hold,
                 survivors must stay bit-exact against PRIVATE-COPY
                 (prefix_cache=False) solo references, copy-on-write must
                 have fired for every mid-block tail writer, and zero
                 blocks or references may leak.
  decode-adapter MULTI-TENANT decode (paged LoRA `AdapterPool` + mixed
                 per-request sampling) under adapter-pool churn: while a
                 mixed-adapter batch decodes live, an adapter is hot-
                 reloaded in place (generation-stamped — in-flight
                 holders keep the OLD weights), a fresh tenant load
                 LRU-evicts an idle adapter, a request for the evicted
                 adapter fails typed (`AdapterNotLoaded`), and an unload
                 of a referenced adapter is refused loud. Survivors must
                 be BIT-EXACT vs solo same-adapter references, adapter
                 AND KV refcounts must conserve (zero pinned slots or
                 blocks after drain), with zero post-warmup retraces.
  decode-cp-prefill
                 CONTEXT-PARALLEL chunked prefill (prefill tokens
                 sequence-sharded along the MeshConfig `cp` axis;
                 docs/long_context.md) with the victim killed mid-ring
                 on its SECOND chunk: exactly the victim fails typed,
                 survivors stay bit-exact vs the single-device engine's
                 solo references, the partially-prefilled blocks are
                 reclaimed, zero post-warmup retraces.

Router phases (`router-*`) run the DISTRIBUTED SERVING TIER
(paddle_tpu/inference/router.py over replica.py, threads-as-replicas over
a real exported model) and prove the tier-level invariants: zero lost
idempotent requests across replica failover (every response bit-matches
the single-process Predictor over the SAME exported artifact), capacity
convergence back to N replicas via supervised restart, generation-stamped
responses that never mix weights across a hot-swap, and the router stats
conservation law admitted == completed + failed + timed_out + overloaded
+ cancelled:

  router-none      fault-free control across 3 replicas;
  router-kill      kill one replica under load (heartbeats stop → the
                   watchdog flags it; in-flight + newly-routed requests
                   fail over; the supervised restart restores capacity);
  router-wedge     wedge one replica (requests hold, beats stop): attempts
                   time out at the attempt deadline and fail over; the
                   watchdog kill/restart clears the wedge;
  router-swap      zero-downtime weight hot-swap under sustained traffic:
                   the roll drops nothing, every response bit-matches its
                   stamped generation's single-process outputs, post-swap
                   traffic serves only the new snapshot;
  router-swap-kill a replica is killed exactly as the roll reaches it:
                   SwapFailed + rollback to the OLD generation everywhere
                   (the dead replica restarts onto it), then a clean
                   re-swap completes.

Router STREAMING phases (`router-stream-*`) run client token streams
through the same tier over REAL continuous-batching decode engines
(decode.demo.tiny_engine_slow per replica, seeded by the weight
generation) and prove the mid-stream robustness contract: a stream
interrupted by replica death resumes on a fresh replica from
`prompt + committed tokens` and the client iterator reads ONE token
sequence bit-identical to an uninterrupted solo-engine run; the streams
ledger conservation law streams.admitted == completed + failed +
timed_out + cancelled + in_flight holds both in `stats()` and in the
live Prometheus exposition; a cancelled stream frees its replica-side
KV blocks within a scheduler round (zero leaks); and every failed-over
stream resolves to one merged causal trace (root `router.generate` +
sibling `router.attempt` spans, the resumed attempt carrying
`resumed_from`):

  router-stream-kill   kill the replica carrying live streams
                       mid-generation: every stream fails over and
                       completes bit-exact, zero tokens lost or
                       duplicated, capacity converges back to N;
  router-stream-wedge  SIGSTOP-shaped wedge (tokens stop, beats stop):
                       the watchdog flags the replica and the pumps
                       migrate mid-stream, same bit-exactness bar;
  router-stream-swap   weight hot-swap under live streams: in-flight
                       streams drain or migrate with generation purity
                       (no stream ever mixes tokens from two
                       generations), post-swap streams serve only the
                       new generation's weights.

The real multi-process replica topology (SubprocessReplica over the
coordination store) is exercised by the slow-marked test in
tests/test_router.py.

Run as a script (exits nonzero on any violation — registered as a tier-1
test via tests/test_serving_fault_injection.py):

    python tools/serving_fault_injector.py [--phases crash,decode-kill,...]
"""
from __future__ import annotations

import argparse
import concurrent.futures
import os
import sys
import threading
import time

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if REPO not in sys.path:
    sys.path.insert(0, REPO)
os.environ.setdefault("JAX_PLATFORMS", "cpu")
# 8 virtual devices (same as tests/conftest.py, which drives this file
# as a tier-1 test): the decode-cp-prefill phase needs a cp=4 mesh
if "--xla_force_host_platform_device_count" not in \
        os.environ.get("XLA_FLAGS", ""):
    os.environ["XLA_FLAGS"] = (os.environ.get("XLA_FLAGS", "")
                               + " --xla_force_host_platform_device_count=8"
                               ).strip()
# Run the whole harness under the lock-order/race checker: every named
# framework lock (serving.pool / serving.batcher / aot.* ...) is
# instrumented, and the end of main() asserts no acquisition-order cycles
# and no locks held across XLA dispatch or file IO — so lock-discipline
# regressions in the serving stack fail this tier-1 harness, not prod.
os.environ.setdefault("PADDLE_TPU_LOCKCHECK", "1")
# ... and under the runtime sanitizer (tpu-san): each phase marks its
# entrypoints warm once its own warmup traffic has compiled them, so ANY
# retrace during the faulted traffic (a re-cloned member recompiling, an
# unstable cache key), any host sync inside a dispatch hot region, any
# use-after-donate and any NaN/Inf is a finding — and the end of main()
# asserts there were ZERO, proving the serving/batching/decode/router
# stacks retrace-free and sync-free under faults.
os.environ.setdefault("PADDLE_TPU_SAN", "1")
# ... and under the graph auditor (graphcheck): every executable this
# harness compiles — serving AOT buckets, exported layer calls, decode
# prefill/decode steps — is statically audited at build time (unexpected
# collectives, conv-region layout changes, host transfers, unaliased
# donation, live-memory watermark), and the end of main() asserts ZERO
# findings on the framework's own executables.
os.environ.setdefault("PADDLE_TPU_GRAPHCHECK", "1")
# ... and with distributed tracing LIVE (obs.trace — the default, made
# explicit here so an inherited opt-out is visible): every phase's
# requests run under root spans, the flight recorder's obs.trace /
# obs.flight locks are part of the lockcheck cycle assertions, and each
# phase asserts that every request failing with a postmortem-class typed
# error (DeadlineExceeded / RequestFailed) left a RETAINED trace behind.
os.environ.setdefault("PADDLE_TPU_TRACE", "1")


def _trace_on():
    from paddle_tpu.obs import trace
    return trace.enabled()


def _assert_postmortems(phase, failed_trace_ids, bad):
    """Every postmortem-class failure must resolve to a retained trace
    in the flight recorder (the operator's debugging contract)."""
    if not _trace_on():
        return
    from paddle_tpu.obs import flight
    pinned = flight.recorder().postmortem_ids()
    for i, tid in failed_trace_ids:
        if tid is None:
            bad.append(f"[{phase}] request {i} failed typed but carries "
                       f"no trace_id (postmortem capture dark)")
        elif int(tid, 16) not in pinned:
            bad.append(f"[{phase}] request {i}'s failure trace {tid} "
                       f"was not retained in the postmortem buffer")


def _san_mark_warm():
    """Declare this phase's warmup over (no-op when the operator
    exported PADDLE_TPU_SAN=0): every jit entrypoint seen so far must
    never trace again; fresh entrypoints (a restarted replica reloading
    its model, a hot-swap loading the next generation) start cold."""
    from paddle_tpu.analysis import runtime_san
    if runtime_san.enabled():
        runtime_san.mark_warm()

PHASES = ("crash", "hang", "poison", "corrupt", "none",
          "batch-crash", "batch-hang", "batch-poison",
          "decode-none", "decode-kill", "decode-wedge", "decode-poison",
          "decode-cow", "decode-spec", "decode-adapter",
          "decode-cp-prefill",
          "router-none", "router-kill", "router-wedge",
          "router-swap", "router-swap-kill",
          "router-stream-kill", "router-stream-wedge",
          "router-stream-swap")

POOL_SIZE = 3
N_REQUESTS = 48
DEADLINE = 2.0          # per-request deadline (generous: execution is ~ms)
HANG_SLEEP = 0.9        # how long the wedged member sleeps
HANG_DEADLINE = 0.25    # deadline for requests in the hang phase
CONVERGE_TIMEOUT = 10.0


def _export_model(path):
    """Export a deterministic linear program whose outputs the harness can
    check bit-for-bit against the eager model."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn

    paddle.seed(0)
    model = nn.Linear(8, 4)
    model.eval()
    x = np.zeros((2, 8), np.float32)
    paddle.jit.save(model, path, input_spec=[paddle.to_tensor(x)])
    return model


class _Injector:
    """Per-phase fault hook plus bookkeeping: counts injections and tracks
    per-member execution re-entrancy (a double-leased member would run two
    requests concurrently on one predictor object)."""

    def __init__(self, phase):
        self.phase = phase
        self.active = False     # armed after warmup
        self.lock = threading.Lock()
        self.injected = 0
        self.poison_id = None   # batch-poison: the one doomed request id
        self.in_member = {}     # id(predictor) -> concurrent executions
        self.max_concurrency = 0

    def enter_member(self, pred):
        with self.lock:
            n = self.in_member.get(id(pred), 0) + 1
            self.in_member[id(pred)] = n
            self.max_concurrency = max(self.max_concurrency, n)

    def exit_member(self, pred):
        with self.lock:
            self.in_member[id(pred)] = self.in_member.get(id(pred), 1) - 1

    def hook(self, slot, req, pred):
        if not self.active:
            return
        if self.phase.startswith("batch-"):
            # batched phases target REQUESTS (the hook runs once per
            # request in the formed batch, before the bucketed dispatch)
            kind = self.phase.split("-", 1)[1]
            if kind == "crash":
                # first execution of every 4th request fails its whole
                # batch: exercises split-retry (innocents must recover)
                if req.id % 4 == 0 and req.attempts == 1:
                    with self.lock:
                        self.injected += 1
                    raise RuntimeError(f"injected batch crash (req {req.id})")
            elif kind == "hang":
                if req.id % 10 == 3 and req.attempts == 1:
                    with self.lock:
                        self.injected += 1
                    time.sleep(HANG_SLEEP)
            elif kind == "poison":
                # ONE deterministically-malformed request: raises in the
                # batch (forcing a split) and again alone (surfacing a
                # typed RequestFailed for it and nobody else)
                if req.id == self.poison_id:
                    with self.lock:
                        self.injected += 1
                    raise ValueError(f"injected poison request {req.id}")
            return
        if self.phase == "crash":
            # fail the first execution of every 4th request — on WHICHEVER
            # member picked it up (slot-agnostic on purpose: gating on one
            # slot made the injection count a scheduling lottery — a run
            # where slot 0 never dequeued a candidate first-attempt
            # injected nothing and flaked the harness). Exercises
            # quarantine + retry without starving the phase of successes.
            if req.id % 4 == 0 and req.attempts == 1:
                with self.lock:
                    self.injected += 1
                raise RuntimeError(f"injected crash (req {req.id})")
            return
        if self.phase == "hang":
            # slot-agnostic for the same determinism reason as crash
            if req.id % 6 == 0 and req.attempts == 1:
                with self.lock:
                    self.injected += 1
                time.sleep(HANG_SLEEP)
            return
        if slot != 0:
            return  # poison/corrupt deliberately target ONE member
        if self.phase in ("poison", "corrupt"):
            with self.lock:
                self.injected += 1
            if self.phase == "corrupt":
                import numpy as np

                for name in pred.get_input_names():
                    pred.get_input_handle(name).copy_from_cpu(
                        np.full((2, 8), 777.0, np.float32))
            raise RuntimeError(f"injected {self.phase} fault")


def run_phase(phase, model, path, verbose=True):
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.inference import (
        Config, DeadlineExceeded, Overloaded, RequestFailed, ServingError,
        ServingPool)
    from paddle_tpu.inference.serving import RetryPolicy

    from paddle_tpu.inference import BatchConfig

    batched = phase.startswith("batch-")
    inj = _Injector(phase)
    deadline = HANG_DEADLINE if phase.endswith("hang") else DEADLINE
    pool = ServingPool(
        Config(path), size=POOL_SIZE, max_queue_depth=N_REQUESTS + 8,
        default_timeout=deadline,
        breaker_threshold=3, breaker_reset_timeout=0.25,
        retry=RetryPolicy(max_retries=2, base_delay=0.01, max_delay=0.05),
        hang_grace=0.05, supervise_interval=0.01, fault_hook=inj.hook,
        batching=BatchConfig(buckets=(1, 2, 4), max_wait_ms=5.0)
        if batched else None)

    rng = np.random.RandomState(7)
    batches = [rng.rand(2, 8).astype(np.float32) for _ in range(N_REQUESTS)]
    want = [model(paddle.to_tensor(b)).numpy() for b in batches]

    bad = []
    outcomes = {"ok": 0, "deadline": 0, "overloaded": 0, "failed": 0}

    # warm up (XLA compiles the shared module — and with batching on,
    # every bucket executable via the persistent cache), THEN arm
    if batched:
        pool.warmup()
    pool.infer([batches[0]], timeout=60.0)
    _san_mark_warm()    # faulted traffic below must never trace again
    # traffic request ids start after the warmup infer; doom a mid-run one
    inj.poison_id = 1 + N_REQUESTS // 2
    inj.active = True

    from paddle_tpu.obs import trace as otrace

    def one_request(i):
        def fn(pred):
            inj.enter_member(pred)
            try:
                # handle-style on purpose: stale-handle corruption would
                # be visible here if quarantine failed to reset state
                h = pred.get_input_handle(pred.get_input_names()[0])
                h.copy_from_cpu(batches[i])
                return pred.run()
            finally:
                inj.exit_member(pred)
        # every request runs under its own root span (the pool has no
        # router above it here): worker/batcher spans hang off it and a
        # typed failure must pin it as a postmortem
        with otrace.root_span("injector.request", attrs={"i": i}):
            try:
                if batched:
                    # feeds-style: the coalescible path batching uses
                    out, = pool.infer([batches[i]], timeout=deadline)
                else:
                    out, = pool.submit(fn, timeout=deadline).result()
            except DeadlineExceeded as e:
                return i, "deadline", getattr(e, "trace_id", None)
            except Overloaded:
                return i, "overloaded", None
            except RequestFailed as e:
                return i, "failed", getattr(e, "trace_id", None)
            except ServingError as e:  # any other typed error: a bug
                return i, f"unexpected-typed:{type(e).__name__}: {e}", None
            except BaseException as e:  # noqa: BLE001 — untyped = bug
                return i, f"untyped:{type(e).__name__}: {e}", None
            return i, "ok", out

    failed_trace_ids = []
    t0 = time.monotonic()
    with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
        futs = [ex.submit(one_request, i) for i in range(N_REQUESTS)]
        try:
            for f in concurrent.futures.as_completed(futs, timeout=90):
                i, kind, out = f.result()
                if kind == "ok":
                    outcomes["ok"] += 1
                    if not np.allclose(out, want[i], rtol=1e-5, atol=1e-6):
                        bad.append(f"[{phase}] request {i} completed with "
                                   f"WRONG output (stale/corrupt handles?)")
                elif kind in outcomes:
                    outcomes[kind] += 1
                    if kind in ("deadline", "failed") and _trace_on():
                        failed_trace_ids.append((i, out))
                else:
                    bad.append(f"[{phase}] request {i} -> {kind}")
        except concurrent.futures.TimeoutError:
            bad.append(f"[{phase}] requests HUNG: "
                       f"{sum(not f.done() for f in futs)} unresolved "
                       f"after 90s — a request escaped its deadline")
            for f in futs:
                f.cancel()
    wall = time.monotonic() - t0

    # postmortem contract: each typed failure above left a retained trace
    _assert_postmortems(phase, failed_trace_ids, bad)

    if inj.max_concurrency > 1:
        bad.append(f"[{phase}] double-lease: {inj.max_concurrency} requests "
                   f"executed concurrently on one member")
    if phase != "none" and inj.injected == 0:
        bad.append(f"[{phase}] harness error: no fault was injected")
    if phase == "none" and outcomes["ok"] != N_REQUESTS:
        bad.append(f"[{phase}] control run lost requests: {outcomes}")
    if phase in ("crash", "corrupt") and outcomes["ok"] < N_REQUESTS * 3 // 4:
        bad.append(f"[{phase}] too few successes despite retries: {outcomes}")
    if phase == "poison" and pool.stats()["breaker_trips"] < 1:
        bad.append(f"[{phase}] poisoned slot never tripped its breaker")
    if batched:
        bs = pool.stats()["batch"]
        multi = sum(v for k, v in bs["executed_by_bucket"].items() if k > 1)
        # a SPLIT multi-request batch never reaches dispatch (so it's
        # absent from executed_by_bucket) but proves formation just the
        # same — under batch-crash it's legal for every multi-request
        # batch to contain a crash candidate and split
        if multi == 0 and bs["split_requests"] < 2:
            bad.append(f"[{phase}] batching never formed a multi-request "
                       f"batch: {bs['executed_by_bucket']}, "
                       f"split_requests={bs['split_requests']}")
        acc = sum(k * v for k, v in bs["executed_by_bucket"].items())
        if acc != bs["requests"] + bs["padded_examples"]:
            bad.append(f"[{phase}] batch accounting violated: "
                       f"sum(bucket*dispatches)={acc} != requests+padding="
                       f"{bs['requests']}+{bs['padded_examples']}")
    if phase == "batch-crash" and outcomes["ok"] != N_REQUESTS:
        bad.append(f"[{phase}] split retry lost innocent batchmates: "
                   f"{outcomes}")
    if phase == "batch-poison":
        if outcomes["failed"] != 1 or outcomes["ok"] != N_REQUESTS - 1:
            bad.append(f"[{phase}] the poison request must be the ONLY "
                       f"failure in its batch: {outcomes}")

    # fault lifted: the pool must converge back to full healthy capacity
    inj.active = False
    deadline_at = time.monotonic() + CONVERGE_TIMEOUT
    stats = pool.stats()
    while time.monotonic() < deadline_at:
        stats = pool.stats()
        if (stats["healthy"] == POOL_SIZE and stats["queue_depth"] == 0
                and stats["in_flight"] == 0):
            break
        try:  # traffic drives half-open probes after the poison phase
            pool.infer([batches[0]], timeout=1.0)
        except ServingError:
            pass
        time.sleep(0.05)
    else:
        bad.append(f"[{phase}] pool did NOT converge to full healthy "
                   f"capacity within {CONVERGE_TIMEOUT}s: healthy="
                   f"{stats['healthy']}/{POOL_SIZE}, "
                   f"queue={stats['queue_depth']}, "
                   f"in_flight={stats['in_flight']}, "
                   f"members={stats['members']}")

    # post-fault correctness: every request must serve bit-correct results
    for i in (0, 1, 2):
        try:
            out, = pool.infer([batches[i]], timeout=5.0)
            if not np.allclose(out, want[i], rtol=1e-5, atol=1e-6):
                bad.append(f"[{phase}] post-fault output wrong for "
                           f"request {i}")
        except ServingError as e:
            bad.append(f"[{phase}] post-fault request failed: {e}")

    drained = pool.shutdown(drain_timeout=5.0)
    if not drained:
        bad.append(f"[{phase}] shutdown failed to drain (stuck lease)")
    final = pool.stats()
    lhs = final["admitted"]
    rhs = (final["completed"] + final["failed"] + final["timed_out"]
           + final["cancelled"])
    if lhs != rhs:
        bad.append(f"[{phase}] stats conservation violated: admitted={lhs} "
                   f"!= completed+failed+timed_out+cancelled={rhs} ({final})")
    if final["in_flight"] != 0 or final["queue_depth"] != 0:
        bad.append(f"[{phase}] leaked lease/queue entry after shutdown: "
                   f"{final}")
    if verbose:
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<8} -> {tag}  ({outcomes}, injected="
              f"{inj.injected}, reclones={final['reclones']}, "
              f"wedged={final['wedged']}, trips={final['breaker_trips']}, "
              f"{wall:.1f}s)")
    return bad


# ---------------------------------------------------------------------------
# decode (continuous-batching) phases
# ---------------------------------------------------------------------------

DECODE_SEQS = (  # (prompt seed, prompt len, max_new) — mixed lengths
    (1, 6, 10), (2, 5, 4), (3, 7, 8), (4, 6, 4), (5, 8, 6), (6, 5, 9))
DECODE_VOCAB = 97
STEP_HANG = 0.6
STEP_TIMEOUT = 0.25


def _decode_model():
    """Tiny LLaMA-style config (rope + GQA + swiglu): its random init
    emits VARIED greedy tokens, so a sequencing bug cannot hide behind a
    degenerate repeated-token output."""
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt

    paddle.seed(7)
    m = gpt("gpt_tiny", vocab_size=DECODE_VOCAB, hidden_size=48,
            num_heads=4, num_kv_heads=2, num_layers=2, rope=True,
            swiglu=True, rms_norm=True, max_position_embeddings=64,
            tie_word_embeddings=False)
    m.eval()
    return m


def _decode_prompts():
    import numpy as np

    return {seed: np.random.RandomState(seed).randint(
        0, DECODE_VOCAB, (n,)).astype(np.int32)
        for seed, n, _ in DECODE_SEQS}


def _decode_engine(model, fault_hook=None):
    from paddle_tpu.inference import DecodeEngine

    return DecodeEngine(model, max_length=32, block_size=8,
                        decode_buckets=(1, 2, 4, 8), prefill_buckets=(8,),
                        default_timeout=30.0, step_timeout=STEP_TIMEOUT,
                        step_retries=2, hang_grace=0.05,
                        supervise_interval=0.01, fault_hook=fault_hook)


_DECODE_REFS = {}    # seed -> solo reference tokens (filled on first use)


def _decode_references(model):
    """Per-prompt solo reference tokens from a fault-free engine — the
    bit-identity yardstick every decode phase compares against."""
    if _DECODE_REFS:
        return _DECODE_REFS
    prompts = _decode_prompts()
    with _decode_engine(model) as eng:
        for seed, _, max_new in DECODE_SEQS:
            _DECODE_REFS[seed] = eng.generate(prompts[seed], max_new)
    return _DECODE_REFS


def run_decode_phase(phase, model, verbose=True):
    from paddle_tpu.inference import (DeadlineExceeded, Overloaded,
                                      PoolClosed, RequestFailed,
                                      ServingError)

    bad = []
    refs = _decode_references(model)
    prompts = _decode_prompts()
    kind = phase.split("-", 1)[1]
    victim_idx = 2                       # DECODE_SEQS row the fault targets
    victim_seed = DECODE_SEQS[victim_idx][0]
    inj = {"armed": kind in ("wedge", "poison"), "injected": 0,
           "lock": threading.Lock()}

    def hook(stage, seq_ids, meta):
        with inj["lock"]:
            if not inj["armed"]:
                return
            if kind == "wedge" and stage == "decode" and len(seq_ids) > 1:
                inj["armed"] = False
                inj["injected"] += 1
            elif kind == "poison" and stage == "prefill" \
                    and seq_ids == [victim_idx + 1]:
                inj["armed"] = False
                inj["injected"] += 1
                raise ValueError(
                    f"injected poisoned feed for sequence {seq_ids[0]}")
            else:
                return
        if kind == "wedge":              # sleep OUTSIDE the bookkeeping lock
            time.sleep(STEP_HANG)

    t0 = time.monotonic()
    eng = _decode_engine(model, fault_hook=hook if kind != "none" else None)
    # compile (first phase) or disk-load (later phases) every bucket,
    # then arm the retrace sentinel: a wedged-step re-dispatch or a
    # sequence join/leave during the faulted traffic must never compile
    eng.warmup()
    _san_mark_warm()
    streams = {}
    try:
        for seed, _, max_new in DECODE_SEQS:
            # sequence ids are assigned in submission order (1-based), so
            # the poison hook can target the victim row deterministically
            streams[seed] = eng.submit(prompts[seed], max_new)
        if kind == "kill":
            v = streams[victim_seed]
            next(iter(v))                # definitely mid-generation
            v.cancel()
            inj["injected"] += 1
        outcomes = {}
        seq_errors = {}
        for seed, _, _ in DECODE_SEQS:
            s = streams[seed]
            try:
                toks = s.result()
                outcomes[seed] = "ok"
                if toks != refs[seed]:
                    bad.append(f"[{phase}] sequence {seed} tokens diverged "
                               f"from the solo reference: {toks} vs "
                               f"{refs[seed]}")
            except (DeadlineExceeded, Overloaded, PoolClosed,
                    RequestFailed) as e:
                outcomes[seed] = type(e).__name__
                seq_errors[seed] = e
            except ServingError as e:
                outcomes[seed] = f"unexpected-typed:{e}"
                bad.append(f"[{phase}] sequence {seed} -> unexpected typed "
                           f"error: {e}")
            except BaseException as e:  # noqa: BLE001 — untyped = violation
                outcomes[seed] = f"untyped:{type(e).__name__}"
                bad.append(f"[{phase}] sequence {seed} -> UNTYPED error: "
                           f"{type(e).__name__}: {e}")

        ok = sum(1 for v in outcomes.values() if v == "ok")
        if kind in ("none", "wedge") and ok != len(DECODE_SEQS):
            bad.append(f"[{phase}] every sequence must complete bit-correct "
                       f"({'a wedged step is retried, not fatal' if kind == 'wedge' else 'control run'}): {outcomes}")
        if kind == "kill":
            if outcomes[victim_seed] == "ok" or ok != len(DECODE_SEQS) - 1:
                bad.append(f"[{phase}] exactly the cancelled sequence must "
                           f"fail: {outcomes}")
            if streams[victim_seed].status != "cancelled":
                bad.append(f"[{phase}] victim status "
                           f"{streams[victim_seed].status} != cancelled")
        if kind == "poison":
            if outcomes[victim_seed] != "RequestFailed" \
                    or ok != len(DECODE_SEQS) - 1:
                bad.append(f"[{phase}] exactly the poisoned sequence must "
                           f"fail (typed RequestFailed): {outcomes}")
            # the failed sequence's per-sequence trace (prefill span,
            # typed status) must be retained as a postmortem
            _assert_postmortems(
                phase,
                [(victim_seed, getattr(seq_errors.get(victim_seed),
                                       "trace_id", None))], bad)
        if kind in ("wedge", "poison") and inj["injected"] == 0:
            bad.append(f"[{phase}] harness error: no fault was injected")

        st = eng.stats()
        if kind == "wedge" and st["wedged_steps"] < 1:
            bad.append(f"[{phase}] the step pool's hang detection never "
                       f"fired: {st['step_pool']}")
        # engine conservation law
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"])
        if lhs != rhs or st["active"] or st["waiting"]:
            bad.append(f"[{phase}] engine conservation violated: "
                       f"admitted={lhs} != {rhs} (active={st['active']}, "
                       f"waiting={st['waiting']})")
    finally:
        drained = eng.shutdown(drain_timeout=10.0)
    if not drained:
        bad.append(f"[{phase}] engine failed to drain")
    # block-pool conservation: nothing leaked through any fault path
    bs = eng.stats()["blocks"]
    if bs["allocated"] != 0 or bs["free"] + bs["reserved"] != bs["total"]:
        bad.append(f"[{phase}] BLOCK LEAK: {bs}")
    if bs["allocs"] != bs["frees"]:
        bad.append(f"[{phase}] alloc/free imbalance: {bs}")
    if verbose:
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<13} -> {tag}  (injected={inj['injected']}, "
              f"steps={eng.stats()['steps']}, "
              f"wedged={eng.stats()['wedged_steps']}, "
              f"peak_blocks={bs['peak_allocated']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


CP_PREFILL_SEQS = ((41, 19, 6), (42, 7, 8), (43, 23, 5), (44, 21, 6))
#                   (seed, prompt_len, max_new) — three of the four
#                   prompts exceed prefill_chunk 8 and so chunk at the
#                   absolute boundaries 8/16, the cp ring's scheduling
#                   units; the 7-token row covers the monolithic path


def _decode_cp_engine(model, mesh, fault_hook=None):
    """CP chunked-prefill engine pair config: IDENTICAL geometry for the
    MeshConfig(cp=4) engine and the single-device reference engine (only
    `mesh` differs), so any token divergence isolates the cp sharding.
    The geometry (incl. num_blocks) matches `_decode_cow_engine`: the
    meshless reference twin then disk-hits the executables the COW phase
    already warmed instead of tripping the tpu-san retrace sentinel with
    a different pool shape at the same fingerprint."""
    from paddle_tpu.inference import DecodeEngine

    return DecodeEngine(model, max_length=48, block_size=8,
                        decode_buckets=(1, 2, 4, 8),
                        prefill_buckets=(8, 16, 24), prefill_chunk=8,
                        num_blocks=57,
                        mesh=mesh, default_timeout=30.0,
                        step_timeout=STEP_TIMEOUT, step_retries=2,
                        hang_grace=0.05, supervise_interval=0.01,
                        fault_hook=fault_hook)


def run_decode_cp_prefill_phase(phase, model, verbose=True):
    """Context-parallel chunked prefill under a mid-ring kill: chunking
    prompts run on a MeshConfig(cp=4) engine (prefill tokens sequence-
    sharded along `cp`, each absolute-boundary chunk one ring-scheduled
    unit) and the victim's SECOND chunk dispatch is killed in flight.
    Exactly the victim fails typed, every survivor's tokens are
    BIT-EXACT vs the single-device engine's solo references, the
    victim's partially-prefilled blocks are reclaimed (pool
    conservation), and the faulted traffic never retraces post-warmup
    (tpu-san)."""
    import numpy as np
    from paddle_tpu.inference import (DeadlineExceeded, Overloaded,
                                      PoolClosed, RequestFailed,
                                      ServingError)
    from paddle_tpu.sharding import MeshConfig

    bad = []
    prompts = {seed: np.random.RandomState(seed).randint(
        0, DECODE_VOCAB, (n,)).astype(np.int32)
        for seed, n, _ in CP_PREFILL_SEQS}

    # solo references from the fault-free SINGLE-DEVICE twin: the cp
    # engine's survivors must reproduce these bit-exact
    refs = {}
    with _decode_cp_engine(model, None) as ref_eng:
        for seed, _, max_new in CP_PREFILL_SEQS:
            refs[seed] = ref_eng.generate(prompts[seed], max_new)

    victim_seed = CP_PREFILL_SEQS[0][0]   # 19 tokens: chunks at 8, 16
    victim_sid = 1                        # submitted first -> engine id 1
    inj = {"armed": True, "injected": 0, "lock": threading.Lock()}

    def hook(stage, seq_ids, meta):
        with inj["lock"]:
            if not inj["armed"] or stage != "prefill":
                return
            if seq_ids == [victim_sid] and meta.get("start", 0) > 0:
                inj["armed"] = False
                inj["injected"] += 1
                raise ValueError("injected mid-ring-prefill kill for "
                                 f"sequence {seq_ids[0]}")

    t0 = time.monotonic()
    eng = _decode_cp_engine(model, MeshConfig(cp=4).build(),
                            fault_hook=hook)
    eng.warmup()
    _san_mark_warm()   # faulted cp traffic below must never trace again
    streams = {}
    try:
        for seed, _, max_new in CP_PREFILL_SEQS:
            streams[seed] = eng.submit(prompts[seed], max_new)
        outcomes = {}
        for seed, _, _ in CP_PREFILL_SEQS:
            s = streams[seed]
            try:
                toks = s.result()
                outcomes[seed] = "ok"
                if toks != refs[seed]:
                    bad.append(f"[{phase}] sequence {seed} tokens "
                               f"diverged from the single-device "
                               f"reference: {toks} vs {refs[seed]}")
            except (DeadlineExceeded, Overloaded, PoolClosed,
                    RequestFailed) as e:
                outcomes[seed] = type(e).__name__
            except ServingError as e:
                outcomes[seed] = f"unexpected-typed:{e}"
                bad.append(f"[{phase}] sequence {seed} -> unexpected "
                           f"typed error: {e}")
            except BaseException as e:  # noqa: BLE001 — untyped = bug
                outcomes[seed] = f"untyped:{type(e).__name__}"
                bad.append(f"[{phase}] sequence {seed} -> UNTYPED error: "
                           f"{type(e).__name__}: {e}")
        ok = sum(1 for v in outcomes.values() if v == "ok")
        if outcomes[victim_seed] != "RequestFailed" \
                or ok != len(CP_PREFILL_SEQS) - 1:
            bad.append(f"[{phase}] exactly the mid-prefill-killed "
                       f"sequence must fail typed: {outcomes}")
        if inj["injected"] == 0:
            bad.append(f"[{phase}] harness error: no fault was injected")
        st = eng.stats()
        if st["prefill_chunks"] < 1:
            bad.append(f"[{phase}] harness error: no prefill was chunked")
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"])
        if lhs != rhs or st["active"] or st["waiting"]:
            bad.append(f"[{phase}] engine conservation violated: "
                       f"admitted={lhs} != {rhs} (active={st['active']}, "
                       f"waiting={st['waiting']})")
    finally:
        drained = eng.shutdown(drain_timeout=10.0)
    if not drained:
        bad.append(f"[{phase}] engine failed to drain")
    bs = eng.stats()["blocks"]
    if bs["allocated"] != 0 or bs["free"] + bs["reserved"] != bs["total"]:
        bad.append(f"[{phase}] BLOCK LEAK: {bs}")
    if bs["allocs"] != bs["frees"]:
        bad.append(f"[{phase}] alloc/free imbalance: {bs}")
    if verbose:
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<13} -> {tag}  (injected={inj['injected']}, "
              f"chunks={eng.stats()['prefill_chunks']}, "
              f"peak_blocks={bs['peak_allocated']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


COW_PREFIX_LEN = 20      # shared system-prompt prefix (mid-block tail:
#                          20 % block_size 8 != 0 — the COW trigger)
COW_SUFFIXES = 4         # sequences extending the prefix privately


def _decode_cow_engine(model, prefix_cache):
    """Prefix-sharing engine pair config: IDENTICAL geometry for the
    sharing engine and the private-copy reference engine (including
    num_blocks, so both disk-hit the same compiled executables)."""
    from paddle_tpu.inference import DecodeEngine

    return DecodeEngine(model, max_length=48, block_size=8,
                        decode_buckets=(1, 2, 4, 8),
                        prefill_buckets=(8, 16, 24), prefill_chunk=8,
                        num_blocks=57, prefix_cache=prefix_cache,
                        default_timeout=30.0, step_timeout=STEP_TIMEOUT,
                        step_retries=2, hang_grace=0.05,
                        supervise_interval=0.01)


def run_decode_cow_phase(phase, model, verbose=True):
    """Prefix-sharing + COW under a mid-decode cancel: one physical copy
    of the shared blocks, survivors bit-exact vs PRIVATE-COPY decode,
    refcount conservation, zero leaked blocks/references."""
    import numpy as np
    from paddle_tpu.inference import (DeadlineExceeded, Overloaded,
                                      PoolClosed, RequestFailed,
                                      ServingError)

    bad = []
    t0 = time.monotonic()
    common = np.random.RandomState(100).randint(
        0, DECODE_VOCAB, (COW_PREFIX_LEN,)).astype(np.int32)
    suffixed = [np.concatenate(
        [common, np.random.RandomState(101 + i).randint(
            0, DECODE_VOCAB, (4,)).astype(np.int32)])
        for i in range(COW_SUFFIXES)]
    prompts = {"canary": common, "dup": common,
               **{f"sfx{i}": p for i, p in enumerate(suffixed)}}
    max_new = {"canary": 4, "dup": 6,
               **{f"sfx{i}": 8 for i in range(COW_SUFFIXES)}}
    victim = "sfx1"

    # private-copy references: same geometry + chunk decomposition, no
    # sharing — the bit-identity yardstick the acceptance bar names
    refs = {}
    with _decode_cow_engine(model, prefix_cache=False) as peng:
        peng.warmup()
        _san_mark_warm()
        for name, p in prompts.items():
            refs[name] = peng.generate(p, max_new[name])

    eng = _decode_cow_engine(model, prefix_cache=True)
    eng.warmup()
    _san_mark_warm()   # faulted shared traffic must never trace again
    outcomes = {}
    try:
        # the canary prefills the shared prefix and publishes it (chunk
        # entries at 8/16 + the full 20-token entry with its mid-block
        # tail); everyone after shares instead of re-prefilling
        if eng.generate(prompts["canary"], max_new["canary"]) \
                != refs["canary"]:
            bad.append(f"[{phase}] canary diverged from its private ref")
        streams = {n: eng.submit(prompts[n], max_new[n])
                   for n in prompts if n != "canary"}
        firsts = {n: next(iter(s)) for n, s in streams.items()}
        for n, tok in firsts.items():
            if tok != refs[n][0]:
                bad.append(f"[{phase}] sequence {n} first token {tok} != "
                           f"private ref {refs[n][0]}")
        # every live sequence + the cache reference the SAME physical
        # prefix blocks: sharing must be observable mid-flight
        bs = eng.stats()["blocks"]
        if bs["shared_refs"] < 1:
            bad.append(f"[{phase}] no shared references observed with "
                       f"{len(streams)} prefix-sharing sequences live: "
                       f"{bs}")
        streams[victim].cancel()
        for n, s in streams.items():
            try:
                toks = s.result()
                outcomes[n] = "ok"
                if toks != refs[n]:
                    bad.append(f"[{phase}] survivor {n} diverged from its "
                               f"private-copy reference: {toks} vs "
                               f"{refs[n]}")
            except PoolClosed:
                outcomes[n] = "cancelled"
            except (DeadlineExceeded, Overloaded, RequestFailed) as e:
                outcomes[n] = type(e).__name__
                bad.append(f"[{phase}] sequence {n} failed unexpectedly: "
                           f"{e}")
            except ServingError as e:
                outcomes[n] = f"unexpected-typed:{e}"
                bad.append(f"[{phase}] {n} -> unexpected typed error: {e}")
            except BaseException as e:  # noqa: BLE001 — untyped = bug
                outcomes[n] = f"untyped:{type(e).__name__}"
                bad.append(f"[{phase}] {n} -> UNTYPED error: "
                           f"{type(e).__name__}: {e}")
        if outcomes.get(victim) != "cancelled":
            bad.append(f"[{phase}] victim outcome {outcomes.get(victim)} "
                       f"!= cancelled")
        if sum(1 for v in outcomes.values() if v == "ok") \
                != len(streams) - 1:
            bad.append(f"[{phase}] exactly the cancelled sequence must "
                       f"fail: {outcomes}")
        st = eng.stats()
        pc = st["prefix_cache"]
        # the dup full-hit skipped prefill entirely; every suffixed
        # sequence matched the 16-token chunk boundary
        if pc["full_hits"] < 1 or pc["hits"] < 1 + COW_SUFFIXES:
            bad.append(f"[{phase}] prefix cache never shared: {pc}")
        if pc["tokens_reused"] < 16 * COW_SUFFIXES + COW_PREFIX_LEN:
            bad.append(f"[{phase}] too few prompt tokens reused: {pc}")
        # canary + dup both write into the shared mid-block tail -> COW
        if st["cow_copies"] < 2:
            bad.append(f"[{phase}] copy-on-write never fired "
                       f"(cow_copies={st['cow_copies']})")
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"])
        if lhs != rhs or st["active"] or st["waiting"]:
            bad.append(f"[{phase}] engine conservation violated: "
                       f"admitted={lhs} != {rhs}")
    finally:
        drained = eng.shutdown(drain_timeout=10.0)
    if not drained:
        bad.append(f"[{phase}] engine failed to drain")
    bs = eng.stats()["blocks"]
    # refcount conservation with sharing: one physical block per id no
    # matter how many holders, nothing leaked through cancel/COW/eviction
    if bs["allocated"] != 0 or bs["free"] + bs["reserved"] != bs["total"]:
        bad.append(f"[{phase}] BLOCK LEAK: {bs}")
    if bs["allocs"] != bs["frees"]:
        bad.append(f"[{phase}] alloc/free imbalance: {bs}")
    if bs["shared_refs"] != 0:
        bad.append(f"[{phase}] dangling shared references after "
                   f"shutdown: {bs}")
    if verbose:
        tag = "FAIL" if bad else "ok"
        st = eng.stats()
        print(f"  {phase:<13} -> {tag}  (hits={st['prefix_cache']['hits']}, "
              f"full={st['prefix_cache']['full_hits']}, "
              f"reused={st['prefix_cache']['tokens_reused']}, "
              f"cow={st['cow_copies']}, chunks={st['prefill_chunks']}, "
              f"peak_blocks={bs['peak_allocated']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


def _adapter_weights(pool, seed):
    """Random LoRA A/B arrays matching the pool's per-layer geometry."""
    import numpy as np

    r = np.random.RandomState(seed)
    return {lname: (r.normal(0, 0.05, a.shape[1:]).astype(np.float32),
                    r.normal(0, 0.05, b.shape[1:]).astype(np.float32))
            for lname, (a, b) in pool.stacks().items()}


def run_decode_adapter_phase(phase, model, verbose=True):
    """Multi-tenant decode under adapter-pool churn: hot reload, LRU
    eviction, and refused unloads race a LIVE mixed-adapter (and
    mixed-sampling) batch. Survivors must stay bit-exact vs solo
    same-adapter references through the SAME warm engine, the evicted
    tenant must fail typed (`AdapterNotLoaded`), a referenced unload
    must be refused loud, and both the adapter pool and the KV block
    pool must conserve (zero pinned slots, zero leaked blocks)."""
    import numpy as np
    from paddle_tpu.inference import (AdapterNotLoaded, AdapterPool,
                                      DecodeEngine, SamplingParams)

    bad = []
    t0 = time.monotonic()
    prompts = _decode_prompts()
    # 4 usable slots (slot 0 is the reserved no-adapter lane), 3 tenants
    # resident: the mid-race reload takes the last free slot and the
    # fresh tenant load must LRU-evict the idle one
    pool = AdapterPool(model, rank=4, slots=5)
    for i in range(3):
        pool.load(f"t{i}", _adapter_weights(pool, 200 + i))
    eng = DecodeEngine(model, max_length=32, block_size=8,
                       decode_buckets=(1, 2, 4, 8), prefill_buckets=(8,),
                       default_timeout=30.0, step_timeout=STEP_TIMEOUT,
                       step_retries=2, hang_grace=0.05,
                       supervise_interval=0.01, adapters=pool)
    eng.warmup()
    _san_mark_warm()   # adapter churn + param mixes must never retrace
    sampled_sp = dict(temperature=0.8, top_k=12, seed=77)
    # (seed, adapter, sampling) per live sequence: tenants t0/t1 mixed
    # with the base model and one seeded sampled request in ONE batch
    live = [(1, None, None), (2, "t0", None), (3, "t1", None),
            (4, "t0", None), (5, "t1", SamplingParams(**sampled_sp))]
    try:
        # solo references through the SAME warm engine — the bit-identity
        # yardstick (t2 serves one solo request so it is resident-idle,
        # the LRU eviction target, when the race begins)
        refs = {}
        for seed, adapter, sp in live:
            refs[seed] = eng.generate(
                prompts[seed], 12, adapter=adapter,
                sampling=None if sp is None else
                SamplingParams(**sampled_sp))
        t2_ref = eng.generate(prompts[6], 8, adapter="t2")
        t0_old_ref = eng.generate(prompts[6], 8, adapter="t0")
        streams = {seed: eng.submit(prompts[seed], 12, adapter=adapter,
                                    sampling=sp)
                   for seed, adapter, sp in live}
        for seed, s in streams.items():
            first = next(iter(s))
            if first != refs[seed][0]:
                bad.append(f"[{phase}] sequence {seed} first token "
                           f"{first} != solo ref {refs[seed][0]}")
        # -- the race: pool churn against the live mixed batch ----------
        # (1) hot reload t0 in place: referenced -> fresh slot, old slot
        # anonymized; in-flight t0 holders keep the OLD generation
        new_t0 = _adapter_weights(pool, 300)
        pool.load("t0", new_t0)
        # (2) fresh tenant: no free slot left -> LRU-evicts idle t2
        pool.load("t3", _adapter_weights(pool, 301))
        # (3) the evicted tenant fails typed at admission
        try:
            eng.submit(prompts[6], 4, adapter="t2")
            bad.append(f"[{phase}] submit for the evicted adapter t2 "
                       f"did not raise AdapterNotLoaded")
        except AdapterNotLoaded:
            pass
        # (4) unloading a referenced adapter is refused loud
        try:
            pool.unload("t1")
            bad.append(f"[{phase}] unload of the referenced adapter t1 "
                       f"was not refused")
        except ValueError as e:
            if "referenced" not in str(e):
                bad.append(f"[{phase}] referenced-unload refusal lost "
                           f"its diagnosis: {e}")
        # (5) a NEW t0 request decodes under the reloaded weights while
        # the old-generation holders are still live
        post_swap = eng.generate(prompts[6], 8, adapter="t0")
        for seed, s in streams.items():
            try:
                toks = s.result()
            except BaseException as e:  # noqa: BLE001 — any failure =
                bad.append(f"[{phase}] sequence {seed} failed under "
                           f"adapter churn: {type(e).__name__}: {e}")
                continue
            if toks != refs[seed]:
                bad.append(f"[{phase}] survivor {seed} diverged from its "
                           f"solo reference under churn: {toks} vs "
                           f"{refs[seed]}")
        # the post-swap t0 output must reproduce solo-under-new-weights
        # (deterministic) and must actually reflect the NEW generation
        if post_swap != eng.generate(prompts[6], 8, adapter="t0"):
            bad.append(f"[{phase}] post-swap t0 decode is not "
                       f"deterministic")
        if post_swap == t0_old_ref:
            bad.append(f"[{phase}] reloaded t0 weights never took "
                       f"effect (old-generation == new-generation "
                       f"outputs: {post_swap})")
        # evict -> hot-load round-trip: re-loading the evicted tenant's
        # weights must reproduce its pre-eviction output bit-exactly
        pool.load("t2", _adapter_weights(pool, 202))
        if eng.generate(prompts[6], 8, adapter="t2") != t2_ref:
            bad.append(f"[{phase}] re-loaded t2 diverged from its "
                       f"pre-eviction output")
        st = eng.stats()
        ast = st["adapters"]
        if ast["evictions"] < 1:
            bad.append(f"[{phase}] LRU eviction never fired: {ast}")
        if ast["swaps"] < 1:
            bad.append(f"[{phase}] generation-stamped reload never "
                       f"swapped: {ast}")
        if ast["refs"] != 0 or ast["pinned_anonymous"] != 0:
            bad.append(f"[{phase}] ADAPTER REFCOUNT LEAK after drain: "
                       f"{ast}")
        if st["sampled"] < 1:
            bad.append(f"[{phase}] the sampled lane never ran: {st}")
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"])
        if lhs != rhs or st["active"] or st["waiting"]:
            bad.append(f"[{phase}] engine conservation violated: "
                       f"admitted={lhs} != {rhs}")
    finally:
        drained = eng.shutdown(drain_timeout=10.0)
    if not drained:
        bad.append(f"[{phase}] engine failed to drain")
    bs = eng.stats()["blocks"]
    if bs["allocated"] != 0 or bs["free"] + bs["reserved"] != bs["total"]:
        bad.append(f"[{phase}] BLOCK LEAK: {bs}")
    if bs["allocs"] != bs["frees"]:
        bad.append(f"[{phase}] alloc/free imbalance: {bs}")
    if verbose:
        tag = "FAIL" if bad else "ok"
        ast = eng.stats()["adapters"]
        print(f"  {phase:<13} -> {tag}  (loads={ast['loads']}, "
              f"evictions={ast['evictions']}, swaps={ast['swaps']}, "
              f"hits={ast['hits']}, occupancy={ast['occupancy']:.2f}, "
              f"peak_blocks={bs['peak_allocated']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


def _decode_spec_draft(model):
    """The speculation draft: the target's own init perturbed on one MLP
    block — it agrees with the target often enough that acceptance
    actually pays, but not always, so rejections/corrections (the
    rollback path) genuinely run during the phase."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu.models import gpt

    paddle.seed(7)
    d = gpt("gpt_tiny", vocab_size=DECODE_VOCAB, hidden_size=48,
            num_heads=4, num_kv_heads=2, num_layers=2, rope=True,
            swiglu=True, rms_norm=True, max_position_embeddings=64,
            tie_word_embeddings=False)
    d.eval()
    rng = np.random.RandomState(11)
    perturbed = 0
    for name, p in d.named_parameters():
        if "layers.1.mlp" in name:
            p._value = p._value + np.asarray(
                rng.normal(0, 2e-2, p.shape), p._value.dtype)
            perturbed += 1
    assert perturbed, "draft perturbation filter matched no parameter"
    return d


def run_decode_spec_phase(phase, model, verbose=True):
    """Speculative decoding under faults: a poisoned shared VERIFY
    dispatch must fall back to plain isolated decode (bit-exact
    survivors, zero uncommitted tokens delivered), a mid-generation
    cancel must spare its round-mates, and both block pools (draft +
    target) must conserve through every path."""
    from paddle_tpu.inference import (DeadlineExceeded, DecodeEngine,
                                      Overloaded, PoolClosed,
                                      RequestFailed, ServingError)

    bad = []
    t0 = time.monotonic()
    refs = _decode_references(model)
    prompts = _decode_prompts()
    draft = _decode_spec_draft(model)
    victim_seed = DECODE_SEQS[2][0]
    inj = {"armed": True, "injected": 0, "lock": threading.Lock()}

    def hook(stage, seq_ids, meta):
        with inj["lock"]:
            if inj["armed"] and stage == "verify" and len(seq_ids) > 1:
                inj["armed"] = False
                inj["injected"] += 1
                raise ValueError(
                    f"injected poisoned verify dispatch for sequences "
                    f"{seq_ids}")

    # geometry shared with _decode_engine so the target-side executables
    # disk-hit; only the draft/propose/verify programs compile here (one
    # bucket — the harness budget; cross-bucket identity is proven by
    # comparing against the references' solo bucket-1 decodes)
    eng = DecodeEngine(model, max_length=32, block_size=8,
                       decode_buckets=(8,), prefill_buckets=(8,),
                       default_timeout=30.0, step_timeout=STEP_TIMEOUT,
                       step_retries=2, hang_grace=0.05,
                       supervise_interval=0.01, fault_hook=hook,
                       draft_model=draft, speculate_k=3)
    eng.warmup()
    _san_mark_warm()   # speculation traffic must never compile again
    streams = {}
    outcomes = {}
    try:
        for seed, _, max_new in DECODE_SEQS:
            streams[seed] = eng.submit(prompts[seed], max_new)
        v = streams[victim_seed]
        next(iter(v))                  # definitely mid-generation
        v.cancel()
        for seed, _, _ in DECODE_SEQS:
            s = streams[seed]
            try:
                toks = s.result()
                outcomes[seed] = "ok"
                if toks != refs[seed]:
                    bad.append(f"[{phase}] sequence {seed} diverged from "
                               f"the non-speculative reference: {toks} "
                               f"vs {refs[seed]}")
            except PoolClosed:
                outcomes[seed] = "cancelled"
            except (DeadlineExceeded, Overloaded, RequestFailed) as e:
                outcomes[seed] = type(e).__name__
                bad.append(f"[{phase}] sequence {seed} failed "
                           f"unexpectedly: {e}")
            except ServingError as e:
                outcomes[seed] = f"unexpected-typed:{e}"
                bad.append(f"[{phase}] sequence {seed} -> unexpected "
                           f"typed error: {e}")
            except BaseException as e:  # noqa: BLE001 — untyped = bug
                outcomes[seed] = f"untyped:{type(e).__name__}"
                bad.append(f"[{phase}] sequence {seed} -> UNTYPED error: "
                           f"{type(e).__name__}: {e}")
        if outcomes.get(victim_seed) != "cancelled":
            bad.append(f"[{phase}] victim outcome "
                       f"{outcomes.get(victim_seed)} != cancelled")
        ok = sum(1 for o in outcomes.values() if o == "ok")
        if ok != len(DECODE_SEQS) - 1:
            bad.append(f"[{phase}] exactly the cancelled sequence must "
                       f"fail: {outcomes}")
        if inj["injected"] == 0:
            bad.append(f"[{phase}] harness error: no verify dispatch was "
                       f"ever poisoned")
        st = eng.stats()
        sp = st["speculative"]
        if not sp["enabled"] or sp["proposed"] == 0 or sp["committed"] == 0:
            bad.append(f"[{phase}] speculation never ran: {sp}")
        if sp["fallbacks"] < 1:
            bad.append(f"[{phase}] the poisoned verify dispatch never "
                       f"fell back to plain decode: {sp}")
        if sp["accepted"] == 0:
            bad.append(f"[{phase}] the draft never had a proposal "
                       f"accepted — speculation was vacuous: {sp}")
        if sp["rejected"] == 0:
            bad.append(f"[{phase}] the perturbed draft never DISAGREED "
                       f"with the target — the rejection/rollback path "
                       f"ran vacuously: {sp}")
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"])
        if lhs != rhs or st["active"] or st["waiting"]:
            bad.append(f"[{phase}] engine conservation violated: "
                       f"admitted={lhs} != {rhs}")
    finally:
        drained = eng.shutdown(drain_timeout=10.0)
    if not drained:
        bad.append(f"[{phase}] engine failed to drain")
    # BOTH pools must conserve: zero leaked blocks/references — an
    # uncommitted speculative token leaking a draft row would show here
    final = eng.stats()
    for key in ("blocks", "draft_blocks"):
        bs = final[key]
        if bs["allocated"] != 0 or bs["free"] + bs["reserved"] \
                != bs["total"]:
            bad.append(f"[{phase}] BLOCK LEAK in {bs['name']} pool: {bs}")
        if bs["allocs"] != bs["frees"]:
            bad.append(f"[{phase}] alloc/free imbalance in {bs['name']} "
                       f"pool: {bs}")
        if bs["shared_refs"] != 0:
            bad.append(f"[{phase}] dangling shared references in "
                       f"{bs['name']} pool: {bs}")
    if verbose:
        sp = final["speculative"]
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<13} -> {tag}  (rounds={sp['rounds']}, "
              f"accepted={sp['accepted']}/{sp['proposed']}, "
              f"rolled_back={sp['rejected']}, "
              f"per_dispatch={sp['accepted_per_dispatch']:.2f}, "
              f"fallbacks={sp['fallbacks']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


# ---------------------------------------------------------------------------
# router (distributed serving tier) phases
# ---------------------------------------------------------------------------

ROUTER_SIZE = 3
ROUTER_REQUESTS = 48
ROUTER_DEADLINE = 3.0
ROUTER_VICTIM = "replica-1"
GEN_A, GEN_B = 1, 2


def _export_router_models(workdir):
    """Two committed model dirs (different weights, same program shape)
    plus single-process Predictor reference outputs — the bit-match
    yardstick for every router phase."""
    import numpy as np
    import paddle_tpu as paddle
    from paddle_tpu import nn
    from paddle_tpu.inference import Config, Predictor, commit_model_dir

    rng = np.random.RandomState(11)
    batches = [rng.rand(2, 8).astype(np.float32)
               for _ in range(ROUTER_REQUESTS)]
    ctx = {"batches": batches, "dirs": {}, "refs": {}}
    for gen, seed in ((GEN_A, 0), (GEN_B, 1)):
        d = os.path.join(workdir, f"router-gen{gen}")
        os.makedirs(d)
        paddle.seed(seed)
        model = nn.Linear(8, 4)
        model.eval()
        x = np.zeros((2, 8), np.float32)
        paddle.jit.save(model, os.path.join(d, "model"),
                        input_spec=[paddle.to_tensor(x)])
        commit_model_dir(d, gen)
        pred = Predictor(Config(os.path.join(d, "model")))
        ctx["dirs"][gen] = d
        ctx["refs"][gen] = [pred.run([b])[0] for b in batches]
    return ctx


def run_router_phase(phase, ctx, verbose=True):
    import numpy as np
    from paddle_tpu.inference import (
        Config, LocalHeartbeats, LocalReplica, Predictor, RouterConfig,
        ServingError, ServingRouter, SwapFailed)
    from paddle_tpu.inference.serving import RetryPolicy

    bad = []
    batches, dirs, refs = ctx["batches"], ctx["dirs"], ctx["refs"]
    hb = LocalHeartbeats()
    registry = {}
    swapkill_armed = {"on": phase == "router-swap-kill"}

    def factory(rid, model_dir, generation):
        def make(d):
            # router-swap-kill: the victim dies EXACTLY as the roll
            # rebuilds it on the new weights — the most adversarial
            # interruption point (mid-_swap_one, post-drain)
            if swapkill_armed["on"] and rid == ROUTER_VICTIM \
                    and d == dirs[GEN_B]:
                swapkill_armed["on"] = False
                registry[rid].kill()
            return Predictor(Config(os.path.join(d, "model")))

        rep = LocalReplica(
            rid, make, model_dir, generation, heartbeat=hb,
            heartbeat_interval=0.02,
            pool_kwargs=dict(default_timeout=ROUTER_DEADLINE,
                             supervise_interval=0.01, hang_grace=0.05,
                             max_queue_depth=ROUTER_REQUESTS + 8))
        registry[rid] = rep
        return rep

    cfg = RouterConfig(
        heartbeat_ttl=0.25, supervise_interval=0.02, start_grace=5.0,
        attempt_timeout=0.5, probe_timeout=10.0, no_capacity_wait=2.0,
        breaker_reset_timeout=0.2,
        restart_backoff=RetryPolicy(base_delay=0.05, max_delay=0.3),
        failover=RetryPolicy(max_retries=4, base_delay=0.002,
                             max_delay=0.01, max_elapsed=20.0))
    t0 = time.monotonic()
    router = ServingRouter(factory, size=ROUTER_SIZE,
                           model_dir=dirs[GEN_A], generation=GEN_A,
                           config=cfg)
    outcomes = {"ok": 0}
    gens_seen = set()
    failed_trace_ids = []
    olock = threading.Lock()

    def one_request(i):
        try:
            outs, gen = router.infer_stamped([batches[i]],
                                             timeout=ROUTER_DEADLINE)
        except ServingError as e:
            with olock:
                k = type(e).__name__
                outcomes[k] = outcomes.get(k, 0) + 1
                if getattr(type(e), "_trace_postmortem", False) \
                        and _trace_on():
                    # the router minted the root span; its typed
                    # failures must resolve to retained traces
                    failed_trace_ids.append(
                        (i, getattr(e, "trace_id", None)))
            return
        except BaseException as e:  # noqa: BLE001 — untyped = violation
            bad.append(f"[{phase}] request {i} -> UNTYPED "
                       f"{type(e).__name__}: {e}")
            return
        with olock:
            outcomes["ok"] += 1
            gens_seen.add(gen)
        if gen not in refs:
            bad.append(f"[{phase}] request {i} stamped unknown "
                       f"generation {gen}")
        elif not np.array_equal(outs[0], refs[gen][i]):
            # bit-match against the stamped generation's single-process
            # outputs: a mixed-weights response can never hide
            bad.append(f"[{phase}] request {i} diverged from its stamped "
                       f"generation {gen}'s single-process outputs")

    try:
        router.warmup(feeds=[batches[0]])
        _san_mark_warm()   # replica restarts / swaps load FRESH layer
        # instances (cold entrypoints) — those may compile; these must not

        with concurrent.futures.ThreadPoolExecutor(max_workers=8) as ex:
            if phase in ("router-kill", "router-wedge"):
                # deterministic mid-stream fault: land it with most of
                # the traffic still to come (a wall-clock timer raced the
                # traffic and could fire after it had all drained)
                head = [ex.submit(one_request, i) for i in range(8)]
                concurrent.futures.wait(head, timeout=30)
                if phase == "router-kill":
                    registry[ROUTER_VICTIM].kill()
                else:
                    registry[ROUTER_VICTIM].wedge()
                futs = head + [ex.submit(one_request, i)
                               for i in range(8, ROUTER_REQUESTS)]
            elif phase in ("router-swap", "router-swap-kill"):
                # sustained traffic around the roll: half the requests
                # before/while it runs, half after
                futs = [ex.submit(one_request, i)
                        for i in range(ROUTER_REQUESTS // 2)]
                time.sleep(0.05)
                if phase == "router-swap":
                    new_gen = router.swap_weights(dirs[GEN_B],
                                                  drain_timeout=10.0)
                    if new_gen != GEN_B:
                        bad.append(f"[{phase}] swap returned generation "
                                   f"{new_gen}, wanted {GEN_B}")
                else:
                    try:
                        router.swap_weights(dirs[GEN_B], drain_timeout=10.0)
                        bad.append(f"[{phase}] swap SUCCEEDED despite the "
                                   f"victim dying mid-roll")
                    except SwapFailed:
                        pass  # expected: rollback engaged
                    if router.stats()["generation"] != GEN_A:
                        bad.append(f"[{phase}] interrupted swap left "
                                   f"generation "
                                   f"{router.stats()['generation']}, "
                                   f"wanted rollback to {GEN_A}")
                futs += [ex.submit(one_request, i)
                         for i in range(ROUTER_REQUESTS // 2,
                                        ROUTER_REQUESTS)]
            else:
                futs = [ex.submit(one_request, i)
                        for i in range(ROUTER_REQUESTS)]
            concurrent.futures.wait(futs, timeout=90)
            hung = sum(not f.done() for f in futs)
            if hung:
                bad.append(f"[{phase}] {hung} requests HUNG past every "
                           f"deadline")

        # --- phase-specific invariants --------------------------------
        if phase in ("router-none", "router-kill", "router-wedge"):
            if outcomes["ok"] != ROUTER_REQUESTS:
                bad.append(f"[{phase}] lost idempotent requests: "
                           f"{outcomes} (want {ROUTER_REQUESTS} ok)")
        if phase == "router-swap":
            if outcomes["ok"] != ROUTER_REQUESTS:
                bad.append(f"[{phase}] the roll dropped requests: "
                           f"{outcomes}")
            if gens_seen != {GEN_A, GEN_B}:
                bad.append(f"[{phase}] traffic did not span the roll: "
                           f"stamped generations {sorted(gens_seen)}")
            if router.stats()["generation"] != GEN_B:
                bad.append(f"[{phase}] router generation "
                           f"{router.stats()['generation']} != {GEN_B}")

        # --- convergence: full healthy capacity on ONE generation ------
        want_gen = GEN_B if phase == "router-swap" else GEN_A
        deadline_at = time.monotonic() + CONVERGE_TIMEOUT
        stats = router.stats()
        while time.monotonic() < deadline_at:
            stats = router.stats()
            if stats["ready"] == ROUTER_SIZE and all(
                    m["generation"] == want_gen for m in stats["members"]):
                break
            time.sleep(0.05)
        else:
            bad.append(f"[{phase}] tier did NOT converge to "
                       f"{ROUTER_SIZE} ready replicas on generation "
                       f"{want_gen}: {stats['members']}")

        if phase in ("router-kill", "router-wedge"):
            # checked AFTER convergence: wedge detection (stale
            # heartbeat -> watchdog) is asynchronous by design
            if router.stats()["deaths"] < 1:
                bad.append(f"[{phase}] the victim was never marked dead")
            if router.stats()["failovers"] < 1:
                bad.append(f"[{phase}] no request ever failed over "
                           f"(40 requests followed the fault)")

        if phase == "router-swap-kill":
            # after rolling back + healing, a clean swap must complete
            new_gen = router.swap_weights(dirs[GEN_B], drain_timeout=10.0)
            if new_gen != GEN_B:
                bad.append(f"[{phase}] post-heal swap returned {new_gen}")
            want_gen = GEN_B

        # post-fault correctness on the converged generation
        for i in (0, 1, 2):
            try:
                outs, gen = router.infer_stamped([batches[i]], timeout=5.0)
                if gen != want_gen or not np.array_equal(
                        outs[0], refs[want_gen][i]):
                    bad.append(f"[{phase}] post-fault output wrong "
                               f"(gen {gen}, want {want_gen})")
            except ServingError as e:
                bad.append(f"[{phase}] post-fault request failed: {e}")
    finally:
        drained = router.shutdown(drain_timeout=10.0)
    _assert_postmortems(phase, failed_trace_ids, bad)
    if not drained:
        bad.append(f"[{phase}] router failed to drain on shutdown")
    final = router.stats()
    lhs = final["admitted"]
    rhs = (final["completed"] + final["failed"] + final["timed_out"]
           + final["overloaded"] + final["cancelled"])
    if lhs != rhs:
        bad.append(f"[{phase}] ROUTER conservation violated: "
                   f"admitted={lhs} != completed+failed+timed_out+"
                   f"overloaded+cancelled={rhs} ({final})")
    if verbose:
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<16} -> {tag}  ({outcomes}, "
              f"deaths={final['deaths']}, failovers={final['failovers']}, "
              f"restarts={final['restarts']}, swaps={final['swaps']}, "
              f"rollbacks={final['swap_rollbacks']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


# ---------------------------------------------------------------------------
# router streaming (HA decode tier) phases
# ---------------------------------------------------------------------------

STREAM_TIER = 3
STREAM_COUNT = 6            # concurrent client streams per phase
STREAM_MAX_NEW = 12
STREAM_GEN_A, STREAM_GEN_B = 1, 2


def _export_stream_ctx(workdir):
    """Commit-stamped (artifact-free) model dirs for the streaming
    phases — the decode weights come from the demo engine factory,
    seeded by the dir's generation stamp — plus SOLO-engine reference
    token sequences, the bit-match yardstick for every streamed
    generation (the decode phases already prove multi-sequence batching
    matches solo runs; here the same bar spans replica failover)."""
    from paddle_tpu.inference import commit_model_dir
    from paddle_tpu.inference.decode.demo import demo_prompt, tiny_engine

    prompts = [demo_prompt(40 + i, 8) for i in range(STREAM_COUNT)]
    ctx = {"prompts": prompts, "dirs": {}, "refs": {}}
    for gen in (STREAM_GEN_A, STREAM_GEN_B):
        d = os.path.join(workdir, f"stream-gen{gen}")
        os.makedirs(d)
        commit_model_dir(d, gen)
        ctx["dirs"][gen] = d
        eng = tiny_engine(gen)
        ctx["refs"][gen] = [list(eng.generate(p, STREAM_MAX_NEW))
                            for p in prompts]
        eng.shutdown()
    return ctx


def run_router_stream_phase(phase, ctx, mserver_url, verbose=True):
    import urllib.request

    from paddle_tpu.inference import (
        LocalHeartbeats, LocalReplica, RouterConfig, ServingError,
        ServingRouter)
    from paddle_tpu.inference.decode.demo import tiny_engine_slow
    from paddle_tpu.inference.serving import RetryPolicy, _NullPredictor

    bad = []
    prompts, dirs, refs = ctx["prompts"], ctx["dirs"], ctx["refs"]
    kind = phase.rsplit("-", 1)[1]
    hb = LocalHeartbeats()
    registry = {}

    def engine_factory(gen):
        # throttled (~50ms/dispatch — wider than the demo default) so a
        # generation spans enough wall-clock that the fault below lands
        # mid-stream deterministically; warmup compiles/disk-hits every
        # bucket up front so faulted traffic never traces
        eng = tiny_engine_slow(
            int(gen), fault_hook=lambda tag, ids, info: time.sleep(0.05))
        eng.warmup()
        return eng

    def factory(rid, model_dir, generation):
        rep = LocalReplica(
            rid, lambda d: _NullPredictor(), model_dir=model_dir,
            generation=generation, heartbeat=hb,
            heartbeat_interval=0.02, decode_factory=engine_factory,
            pool_kwargs=dict(default_timeout=30.0,
                             supervise_interval=0.01, hang_grace=0.05))
        registry[rid] = rep
        return rep

    cfg = RouterConfig(
        # ttl is looser than the infer phases': engine builds compile
        # under instrumented harnesses, and a starved beat thread must
        # not read as a death mid-swap
        heartbeat_ttl=1.0, supervise_interval=0.02, start_grace=30.0,
        attempt_timeout=2.0, probe_timeout=10.0, no_capacity_wait=5.0,
        breaker_reset_timeout=0.2, affinity_block_tokens=8,
        restart_backoff=RetryPolicy(base_delay=0.05, max_delay=0.3),
        failover=RetryPolicy(max_retries=5, base_delay=0.002,
                             max_delay=0.01, max_elapsed=40.0))
    t0 = time.monotonic()
    name = f"stream_{kind}"
    router = ServingRouter(factory, size=STREAM_TIER,
                           model_dir=dirs[STREAM_GEN_A],
                           generation=STREAM_GEN_A, config=cfg,
                           heartbeats=hb, name=name)
    olock = threading.Lock()

    def run_stream(i, want_gen=None):
        """Submit prompt i, consume the stream to completion, bit-check
        the ONE token sequence the client iterator saw against the
        stamped generation's solo reference."""
        try:
            rs = router.submit_generate(prompts[i], STREAM_MAX_NEW,
                                        timeout=30.0)
            toks = list(rs.result())
        except ServingError as e:
            return ("typed", type(e).__name__, None)
        except BaseException as e:  # noqa: BLE001 — untyped = violation
            with olock:
                bad.append(f"[{phase}] stream {i} -> UNTYPED "
                           f"{type(e).__name__}: {e}")
            return ("untyped", type(e).__name__, None)
        gen = rs.generation
        with olock:
            if gen not in refs:
                bad.append(f"[{phase}] stream {i} stamped unknown "
                           f"generation {gen}")
            elif toks != refs[gen][i]:
                # the ONE-sequence guarantee: resumed output must be
                # bit-identical to an uninterrupted solo run — a lost,
                # duplicated, or mixed-weights token can never hide
                bad.append(f"[{phase}] stream {i} diverged from its "
                           f"stamped generation {gen}'s solo reference: "
                           f"{toks} vs {refs[gen][i]}")
            elif want_gen is not None and gen != want_gen:
                bad.append(f"[{phase}] stream {i} stamped generation "
                           f"{gen}, wanted {want_gen}")
        return ("ok", gen, rs)

    def _live_victim(timeout=15.0):
        deadline_at = time.monotonic() + timeout
        while time.monotonic() < deadline_at:
            carrying = [m for m in router.stats()["members"]
                        if m["streams"] > 0 and m["state"] == "ready"]
            if carrying:
                return max(carrying, key=lambda m: m["streams"])["rid"]
            time.sleep(0.01)
        return None

    try:
        # warm control stream: proves the fault-free path and flushes
        # the first-dispatch compiles before the retrace sentinel arms
        if run_stream(0)[0] != "ok":
            bad.append(f"[{phase}] warm control stream failed")
        _san_mark_warm()   # replica restarts / swaps build FRESH engines
        # (cold entrypoints) — those may compile; these must not

        results = []
        with concurrent.futures.ThreadPoolExecutor(
                max_workers=STREAM_COUNT) as ex:
            futs = [ex.submit(run_stream, i) for i in range(STREAM_COUNT)]
            victim = _live_victim()
            if victim is None:
                bad.append(f"[{phase}] no replica ever carried a live "
                           f"stream — the fault was never landed")
            elif kind == "kill":
                time.sleep(0.1)          # definitely mid-generation
                registry[victim].kill()
            elif kind == "wedge":
                time.sleep(0.1)
                registry[victim].wedge()
            else:                        # swap under live streams
                new_gen = router.swap_weights(dirs[STREAM_GEN_B],
                                             drain_timeout=20.0)
                if new_gen != STREAM_GEN_B:
                    bad.append(f"[{phase}] swap returned generation "
                               f"{new_gen}, wanted {STREAM_GEN_B}")
            done, pending = concurrent.futures.wait(futs, timeout=120)
            if pending:
                bad.append(f"[{phase}] {len(pending)} streams HUNG past "
                           f"every deadline")
            results = [f.result() for f in done]

        ok = sum(1 for r in results if r[0] == "ok")
        if kind in ("kill", "wedge"):
            # failover is lossless for streams: every client iterator
            # completes (resumed mid-stream on a fresh replica)
            if ok != STREAM_COUNT:
                bad.append(f"[{phase}] lost streams across the fault: "
                           f"{ok}/{STREAM_COUNT} completed "
                           f"({[r[:2] for r in results]})")
            st = router.stats()["streams"]
            if st["failovers"] < 1 or st["resumed"] < 1:
                bad.append(f"[{phase}] no stream ever failed over / "
                           f"resumed mid-generation: {st}")
        else:
            # the roll may typed-fail a stream caught between
            # generations (purity > availability) but never silently
            # splice; completed streams are bit-checked by run_stream
            for r in results:
                if r[0] == "typed" and r[1] not in (
                        "RequestFailed", "DeadlineExceeded"):
                    bad.append(f"[{phase}] stream failed with unexpected "
                               f"typed error {r[1]}")
            gens = {r[1] for r in results if r[0] == "ok"}
            if not gens <= {STREAM_GEN_A, STREAM_GEN_B}:
                bad.append(f"[{phase}] streams stamped unknown "
                           f"generations {sorted(gens)}")

        # --- convergence: full healthy capacity on ONE generation ------
        want_gen = STREAM_GEN_B if kind == "swap" else STREAM_GEN_A
        deadline_at = time.monotonic() + CONVERGE_TIMEOUT
        stats = router.stats()
        while time.monotonic() < deadline_at:
            stats = router.stats()
            if stats["ready"] == STREAM_TIER and all(
                    m["generation"] == want_gen
                    for m in stats["members"]
                    if m["state"] not in ("retired",)):
                break
            time.sleep(0.05)
        else:
            bad.append(f"[{phase}] tier did NOT converge to "
                       f"{STREAM_TIER} ready replicas on generation "
                       f"{want_gen}: {stats['members']}")

        # post-fault streams on the converged generation
        for i in (0, 1):
            r = run_stream(i, want_gen=want_gen)
            if r[0] != "ok":
                bad.append(f"[{phase}] post-fault stream {i} failed: "
                           f"{r[1]}")

        # --- cancelled stream frees replica-side KV blocks -------------
        rs = router.submit_generate(prompts[0], STREAM_MAX_NEW,
                                    timeout=30.0)
        it = iter(rs)
        next(it)                        # mid-generation, blocks held
        rs.cancel()
        try:
            rs.result(timeout=10.0)
            bad.append(f"[{phase}] cancelled stream completed anyway")
        except ServingError:
            pass
        deadline_at = time.monotonic() + 5.0
        leaks = ["unchecked"]
        while time.monotonic() < deadline_at:
            leaks = []
            for m in router.stats()["members"]:
                rep = registry.get(m["rid"])
                if rep is None or m["state"] != "ready":
                    continue
                d = (rep.stats().get("pool") or {}).get("decode")
                if not d:
                    continue
                # blocks pinned by the prefix cache are deliberate
                # retention, not a leak
                held = (d["blocks"]["allocated"]
                        - d["prefix_cache"]["physical_blocks"])
                if d["active"] or d["waiting"] or d["prefilling"] or held:
                    leaks.append((m["rid"], d["active"], d["waiting"],
                                  held))
            if not leaks:
                break
            time.sleep(0.05)
        if leaks:
            bad.append(f"[{phase}] KV blocks leaked after stream "
                       f"cancel: {leaks}")

        # --- streams ledger: stats() AND the live Prometheus text ------
        st = router.stats()["streams"]
        lhs = st["admitted"]
        rhs = (st["completed"] + st["failed"] + st["timed_out"]
               + st["cancelled"] + st["in_flight"])
        if lhs != rhs:
            bad.append(f"[{phase}] STREAMS conservation violated: "
                       f"admitted={lhs} != completed+failed+timed_out+"
                       f"cancelled+in_flight={rhs} ({st})")
        try:
            text = urllib.request.urlopen(
                mserver_url + "/metrics", timeout=5).read().decode()
        except Exception as e:  # noqa: BLE001 — verdict-reported
            bad.append(f"[{phase}] live metrics scrape failed: "
                       f"{type(e).__name__}: {e}")
        else:
            prefix = f"serving_router_{name}_streams_"
            scraped = {}
            for ln in text.splitlines():
                if ln.startswith(prefix):
                    k, _, v = ln.partition(" ")
                    scraped[k[len(prefix):]] = int(float(v))
            need = ("admitted", "completed", "failed", "timed_out",
                    "cancelled", "in_flight")
            if not all(k in scraped for k in need):
                bad.append(f"[{phase}] streams ledger missing from the "
                           f"scraped exposition: {sorted(scraped)}")
            elif scraped["admitted"] != sum(scraped[k]
                                            for k in need[1:]):
                bad.append(f"[{phase}] scraped streams ledger violates "
                           f"conservation: {scraped}")
            if 'router_ttft_seconds_count{' not in text \
                    or 'replica="' not in text:
                bad.append(f"[{phase}] per-replica router.ttft_seconds "
                           f"histogram missing from the exposition")

        # --- failed-over streams read as ONE merged causal record ------
        if kind in ("kill", "wedge") and _trace_on():
            from paddle_tpu.obs import flight
            rec = flight.recorder()
            merged = 0
            for tr in rec.traces(limit=200):
                spans = rec.spans_for(tr["trace_id"])
                root = next(
                    (s for s in spans if s.name == "router.generate"
                     and s.parent_id is None
                     and (s.attrs or {}).get("router") == name), None)
                if root is None \
                        or int((root.attrs or {}).get("failovers", 0)) < 1:
                    continue
                attempts = [s for s in spans
                            if s.name == "router.attempt"]
                if len(attempts) >= 2 and any(
                        (s.attrs or {}).get("resumed_from")
                        for s in attempts):
                    merged += 1
            if merged < 1:
                bad.append(f"[{phase}] no failed-over stream resolved "
                           f"to one merged causal record (root "
                           f"router.generate + resumed router.attempt)")
    finally:
        drained = router.shutdown(drain_timeout=15.0)
    if not drained:
        bad.append(f"[{phase}] router failed to drain on shutdown")
    final = router.stats()
    if verbose:
        st = final["streams"]
        tag = "FAIL" if bad else "ok"
        print(f"  {phase:<20} -> {tag}  (streams={st['admitted']} "
              f"admitted/{st['completed']} completed, "
              f"failovers={st['failovers']}, resumed={st['resumed']}, "
              f"affinity_hits={st['affinity_hits']}, "
              f"deaths={final['deaths']}, "
              f"{time.monotonic() - t0:.1f}s)")
    return bad


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--phases", default=",".join(PHASES),
                    help="comma-separated fault phases to run "
                         "(default: all + the no-fault control)")
    args = ap.parse_args(argv)
    phases = [p.strip() for p in args.phases.split(",") if p.strip()]
    violations = []
    from paddle_tpu.jit.aot import hermetic_cache

    # batched phases share one hermetic AOT cache: the first warmup REALLY
    # builds the bucket executables (the audit hooks the summary asserts
    # on fire only then), later phases disk-hit
    with hermetic_cache(prefix="serving-fault-") as workdir:
        # Always-on telemetry rides along (paddle_tpu.obs): every pool /
        # engine / router below registers into the process registry, and
        # a live HTTP exporter is scraped CONCURRENTLY with the fault
        # phases — so the obs.registry / obs.http lock discipline (no
        # cycles, nothing held across serialization or dispatch) is
        # proven under the same lockcheck run as the serving stack.
        import urllib.request

        from paddle_tpu.obs import MetricsServer

        mserver = MetricsServer().start()
        scrape_stop = threading.Event()
        scrape_errors: list = []
        scrapes = [0]

        def _scrape_loop():
            while not scrape_stop.wait(0.1):
                try:
                    urllib.request.urlopen(
                        mserver.url + "/metrics", timeout=2).read()
                    scrapes[0] += 1
                except Exception as e:  # noqa: BLE001 — verdict-reported
                    scrape_errors.append(
                        f"concurrent scrape failed: "
                        f"{type(e).__name__}: {e}")

        scraper = threading.Thread(target=_scrape_loop,
                                   name="obs-scraper", daemon=True)
        scraper.start()
        path = os.path.join(workdir, "infer")
        serving_phases = [p for p in phases
                          if not p.startswith(("decode-", "router-"))]
        decode_phases = [p for p in phases if p.startswith("decode-")]
        stream_phases = [p for p in phases
                         if p.startswith("router-stream-")]
        router_phases = [p for p in phases if p.startswith("router-")
                         and not p.startswith("router-stream-")]
        model = _export_model(path) if serving_phases else None
        print("serving fault injection (hook-at-execution):")
        for phase in serving_phases:
            violations += run_phase(phase, model, path)
        if decode_phases:
            # decode phases share one model + one compile cache: the
            # reference engine compiles each bucket once, later phases
            # disk-hit (warm-start reuse is ALSO under test here)
            dmodel = _decode_model()
            if [p for p in decode_phases
                    if p not in ("decode-cow", "decode-adapter",
                                 "decode-cp-prefill")]:
                _decode_references(dmodel)
            for phase in decode_phases:
                if phase == "decode-cow":
                    violations += run_decode_cow_phase(phase, dmodel)
                elif phase == "decode-spec":
                    violations += run_decode_spec_phase(phase, dmodel)
                elif phase == "decode-adapter":
                    violations += run_decode_adapter_phase(phase, dmodel)
                elif phase == "decode-cp-prefill":
                    violations += run_decode_cp_prefill_phase(phase, dmodel)
                else:
                    violations += run_decode_phase(phase, dmodel)
        if router_phases:
            # threads-as-replicas over two committed real-model snapshots
            # (the multi-process topology runs slow-marked in
            # tests/test_router.py)
            rctx = _export_router_models(workdir)
            print("router (distributed serving tier) phases:")
            for phase in router_phases:
                violations += run_router_phase(phase, rctx)
        if stream_phases:
            # streaming through the tier: LocalReplica over real
            # continuous-batching decode engines (the multi-process
            # topology runs slow-marked in tests/test_router.py)
            sctx = _export_stream_ctx(workdir)
            print("router streaming (HA decode tier) phases:")
            for phase in stream_phases:
                violations += run_router_stream_phase(
                    phase, sctx, mserver.url)

        # telemetry verdict: the concurrent scraper must have succeeded
        # throughout, and a final scrape must expose the serving metric
        # families (the pools' conservation-law counters were live on
        # the endpoint for the whole run)
        scrape_stop.set()
        scraper.join(timeout=2.0)
        violations += scrape_errors
        try:
            final = urllib.request.urlopen(
                mserver.url + "/metrics", timeout=5).read().decode()
            hz = urllib.request.urlopen(
                mserver.url + "/healthz", timeout=5).status
        except Exception as e:  # noqa: BLE001 — verdict-reported
            violations.append(f"final metrics scrape failed: "
                              f"{type(e).__name__}: {e}")
        else:
            if hz != 200:
                violations.append(f"/healthz returned {hz}, expected 200")
            if serving_phases and "serving_request_seconds" not in final:
                violations.append(
                    "final scrape is missing the serving_request_seconds "
                    "histogram — pool instrumentation never reached the "
                    "registry")
            if stream_phases and "router_request_seconds" not in final:
                violations.append(
                    "final scrape is missing the router_request_seconds "
                    "histogram — router stream instrumentation never "
                    "reached the registry")
            print(f"obs: {scrapes[0]} concurrent scrapes ok; final "
                  f"exposition {len(final)} bytes")
        mserver.stop()

        if any("hang" in p for p in phases):
            # Wedged members are retired with their threads ABANDONED (by
            # design: capacity is restored with a fresh clone and the
            # sleeper's late result is discarded). Give the last of them
            # time to wake, run, and exit BEFORE the interpreter starts
            # tearing down: a daemon thread reaped mid-XLA-dispatch dies
            # inside C++ and intermittently aborts the whole process
            # ("terminate called without an active exception") after the
            # verdict is already printed.
            time.sleep(HANG_SLEEP + 0.3)

    from paddle_tpu.analysis import runtime_san
    if not runtime_san.enabled():
        # the operator exported PADDLE_TPU_SAN=0 on purpose (e.g. to
        # isolate sanitizer overhead) — phases still gate the run, only
        # the retrace/sync/donation/non-finite assertions are off
        print("tpu-san: disabled by PADDLE_TPU_SAN="
              f"{os.environ.get('PADDLE_TPU_SAN')!r}; "
              "sanitizer assertions skipped")
    else:
        srep = runtime_san.report()
        # guard against a VACUOUS pass: the probes must actually have
        # run — hot regions entered on every dispatch path and traces
        # observed during warmups. An import-order accident that left
        # the sanitizer dark would otherwise "pass" trivially.
        if srep["counters"]["hot_regions"] == 0:
            violations.append(
                "tpu-san was not effective: no hot region was ever "
                "entered (probes dark? PADDLE_TPU_SAN="
                f"{os.environ.get('PADDLE_TPU_SAN')!r})")
        if srep["counters"]["traces"] == 0:
            violations.append(
                "tpu-san was not effective: no jit entrypoint trace was "
                "ever observed despite the warmup compiles")
        for f in srep["findings"]:
            violations.append(
                f"tpu-san {f['detector']} at {f['site']}: {f['message']}")
        n_found = sum(srep["counts"].values())
        c = srep["counters"]
        print(f"tpu-san: {n_found} finding(s); traces={c['traces']}, "
              f"hot_regions={c['hot_regions']}, "
              f"donations={c['donations']}, "
              f"finite_checks={c['finite_checks']} across "
              f"{srep['entrypoints']} entrypoints")

    from paddle_tpu.analysis import graphcheck
    if not graphcheck.enabled():
        # the operator exported PADDLE_TPU_GRAPHCHECK=0 on purpose —
        # phases still gate the run, only the graph-audit assertions
        # are off
        print("graphcheck: disabled by PADDLE_TPU_GRAPHCHECK="
              f"{os.environ.get('PADDLE_TPU_GRAPHCHECK')!r}; "
              "graph-audit assertions skipped")
    else:
        grep = graphcheck.report()
        # vacuity guard (same bar as tpu-san's): the phases above
        # compiled real executables, so the auditor must have run
        if grep["counters"]["audits"] == 0:
            violations.append(
                "graphcheck was not effective: no executable was ever "
                "audited despite the warmup compiles "
                "(PADDLE_TPU_GRAPHCHECK="
                f"{os.environ.get('PADDLE_TPU_GRAPHCHECK')!r})")
        for f in grep["findings"]:
            violations.append(
                f"graphcheck {f['rule']} at {f['site']}: {f['message']}")
        print(f"graphcheck: {sum(grep['counts'].values())} finding(s); "
              f"audits={grep['counters']['audits']}, "
              f"collectives={grep['counters']['collectives_seen']}, "
              f"watermarked_sites={len(grep['watermarks'])}")

    from paddle_tpu.obs import trace as _otrace_verdict
    if not _otrace_verdict.enabled():
        # the operator exported PADDLE_TPU_TRACE=0 on purpose — phases
        # still gate the run, only the trace/postmortem assertions and
        # the obs.trace/obs.flight lock expectations are off
        print("trace: disabled by PADDLE_TPU_TRACE="
              f"{os.environ.get('PADDLE_TPU_TRACE')!r}; "
              "trace assertions skipped")
    else:
        from paddle_tpu.obs import flight as _oflight_verdict
        fstats = _oflight_verdict.recorder().stats()
        # vacuity guard (like tpu-san's): tracing must actually have
        # recorded spans during the phases, or the postmortem
        # assertions above passed trivially
        if fstats["recorded"] == 0:
            violations.append(
                "tracing was not effective: no span was ever recorded "
                "(probes dark? PADDLE_TPU_TRACE="
                f"{os.environ.get('PADDLE_TPU_TRACE')!r})")
        print(f"trace: {fstats['recorded']} spans across "
              f"{fstats['rings']} rings, {fstats['pinned_traces']} "
              f"postmortem trace(s), {fstats['dropped_wraps']} ring "
              f"wraps")

    from paddle_tpu.analysis import lockcheck
    if not lockcheck.enabled():
        # the operator exported PADDLE_TPU_LOCKCHECK=0 on purpose (e.g.
        # to isolate instrumentation overhead) — the serving phases above
        # still gate the run, only the lock-discipline assertions are off
        print("lockcheck: disabled by PADDLE_TPU_LOCKCHECK="
              f"{os.environ.get('PADDLE_TPU_LOCKCHECK')!r}; "
              "lock assertions skipped")
    else:
        rep = lockcheck.report()
        # guard against a VACUOUS pass: if instrumentation never took
        # effect (lockcheck imported before the setdefault above),
        # report() is empty and every assertion below would trivially
        # hold — require the serving stack's own named locks to be seen
        expected_locks = {"serving.pool", "serving.request",
                          "serving.breaker",
                          # telemetry: the registry lock (metric
                          # get-or-create + snapshot bookkeeping) and
                          # the exporter's start/stop lock, exercised by
                          # the concurrent scraper above — both must
                          # stay out of every cycle and never be held
                          # across dispatch/serialization
                          "obs.registry", "obs.http"}
        from paddle_tpu.obs import trace as _otrace_mod
        if _otrace_mod.enabled():
            # tracing live: the span-id generator lock and the flight
            # recorder's registry/postmortem lock are on every traced
            # request path — same 0-cycles / 0-held-across-dispatch bar
            expected_locks |= {"obs.trace", "obs.flight"}
        if any(p.startswith(("decode-", "router-stream-"))
               for p in phases):
            # the decode engine's own named locks must have been observed
            # (and the 0-cycles / 0-held-across-dispatch assertions below
            # now cover the decode-step dispatch path too); the streaming
            # router phases run real decode engines inside each replica,
            # so they put the same locks on the live path
            expected_locks |= {"decode.engine", "decode.block_pool"}
        if "decode-adapter" in phases:
            # the adapter pool's named lock joins the decode dispatch
            # path: same 0-cycles / 0-held-across-dispatch bar
            expected_locks |= {"decode.adapter_pool"}
        if any(p.startswith("router-") for p in phases):
            # the distributed tier's named locks: the same 0-cycles /
            # 0-held-across-dispatch assertions cover the router's
            # routing, supervision, and hot-swap paths
            expected_locks |= {"router.core", "router.replica",
                               "router.heartbeats"}
        missing = expected_locks - set(rep["locks"])
        if missing:
            violations.append(
                f"lockcheck was not effective: named locks never observed "
                f"({sorted(missing)}) — instrumentation off? "
                f"(PADDLE_TPU_LOCKCHECK="
                f"{os.environ.get('PADDLE_TPU_LOCKCHECK')!r})")
        for cyc in rep["cycles"]:
            violations.append("lock acquisition-order cycle: "
                              + " -> ".join(cyc))
        for v in rep["violations"]:
            if not v["warning"]:
                violations.append(f"lockcheck {v['kind']} ({v['thread']}): "
                                  f"{v['message']}")
        checked = sorted(rep["locks"])
        print(f"lockcheck: {len(checked)} named locks observed "
              f"({', '.join(checked)}); {len(rep['cycles'])} cycle(s), "
              f"{sum(1 for v in rep['violations'] if not v['warning'])} "
              "violation(s)")

    for v in violations:
        print("VIOLATION:", v, file=sys.stderr)
    print("RESULT:", "FAIL" if violations else "PASS")
    return 1 if violations else 0


if __name__ == "__main__":
    sys.exit(main())
