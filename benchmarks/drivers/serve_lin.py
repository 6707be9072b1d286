"""Serving cells of a model with recurrent (gated delta-rule) layers beside
full attention: `ServingPool(decode_engine=DecodeEngine(...))`, a state slot
a sequence beside the paged pool.

The window, the clients, the warm traffic and the sample of finished
requests are `drivers/serve.py`'s (a private copy of that module is given
this file's `engine_counters`, which adds the recurrent layers' counters to
every snapshot). What differs is the server (`build_server`: weights drawn a
layer at a time into a model built under `LazyGuard`), a sampler of the
engine's `stats()` through the window (what a sequence holds of the two
kinds of cache), and the reference the served tokens are held against.

The comparison, on what the timed window served: a seeded sample of the
finished requests, the longest among them, each forwarded whole through the
plain reference (one sequence at a time, the recurrence position by
position). `token_gap`, as the dense cell defines it: how far the reference's
logit of a served token lies under the reference's best at its position, the
worst over every served token of the sample. A served token is the product
of the whole path: chunked prefill from a carried state, the state's
hand-over into decode, some hundreds of one-step updates of a float32 state
and as many rows of the full layers' cache. The log also holds the readings of
the two controls on the longest request (the token each control puts first):
the float8 reference, which `token_gap`'s limit has to refuse, and the
reference that keeps the state in bfloat16, which has no limit of its own
(PERF.md section 2 says how the limit stands to it).
"""
from __future__ import annotations

import gc
import os
import threading
import time

import numpy as np

from benchmarks import harness, program_spans, weights_olmo_hybrid
from benchmarks.reference import olmo_hybrid_ref
from benchmarks.traffic import generate

serve = harness.load_module(
    os.path.join(harness.HERE, "drivers", "serve.py"), "driver_serve_for_lin")

LIN_COUNTERS = ("lin_chunk_tokens", "lin_step_tokens", "lin_state_slots",
                "lin_state_slots_peak", "lin_state_bytes",
                "kv_blocks_in_use")


def build_server(cell, w):
    """The program's server for this cell, holding the benchmark's weights.
    The model is built lazily: its own float32 initial values (16 GB at the
    served size) are never made."""
    import paddle_tpu
    from paddle_tpu.inference import DecodeEngine, ServingPool
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    mix = cell["mix"]
    geo = dict(mix["engine"])
    for key in ("decode_buckets", "prefill_buckets"):
        geo[key] = tuple(geo[key])
    with paddle_tpu.LazyGuard():
        net = GPTForCausalLM(GPTConfig(**cell["model"]))
    net.eval()
    names = {n for n, _ in net.named_parameters()}
    if names != set(w):
        raise SystemExit(f"[serve_lin] weights and model differ in "
                         f"{sorted(names ^ set(w))[:6]}")
    for n, p in net.named_parameters():
        p._value = w[n]
    eng = DecodeEngine(net, default_timeout=mix["request_timeout_s"], **geo)
    pool = ServingPool(decode_engine=eng,
                       default_timeout=mix["request_timeout_s"])
    return eng, pool


def free_server(eng, pool):
    """Shut the program down and give its device memory back, buffer by
    buffer, whoever still refers to the engine, so that the reference's copy
    of the weights fits."""
    pool.shutdown()
    eng.shutdown()
    held = [p._value for _, p in eng.model.named_parameters()]
    held += [t for entry in eng.pool.tensors for t in entry]
    for a in held:
        a.delete()
    gc.collect()


def engine_counters(eng):
    st = eng.stats()
    out = {k: st[k] for k in ("steps", "prefills", "prefill_chunks",
                              "tokens_out", "completed", "failed",
                              "timed_out", "wedged_steps",
                              "isolation_rounds", "compiles",
                              "step_active", "step_slots")}
    out["prefix_hits"] = st["prefix_cache"]["hits"]
    out.update({k: st[k] for k in LIN_COUNTERS})
    return out


serve.engine_counters = engine_counters


class CacheSampler:
    """`stats()` read every `every_s` seconds while the window is open: the
    state slots and KV blocks in use and the sequences resident."""

    def __init__(self, eng, every_s):
        self.eng, self.every_s = eng, every_s
        self.samples = []
        self._stop = threading.Event()
        self._thread = threading.Thread(target=self._run, daemon=True,
                                        name="cache-sampler")

    def _run(self):
        while not self._stop.wait(self.every_s):
            st = self.eng.stats()
            self.samples.append({
                "t": time.perf_counter(),
                "resident": st["active"] + st["prefilling"],
                **{k: st[k] for k in ("lin_state_slots", "lin_state_bytes",
                                      "kv_blocks_in_use")}})

    def start(self):
        self._thread.start()

    def stop(self, t_close):
        self._stop.set()
        self._thread.join(5.0)
        return [s for s in self.samples if s["t"] <= t_close]


# ---- the comparison ------------------------------------------------------

def token_gaps(model, mix, w, rec, control=None):
    """For every served token of one finished request: how far its logit
    lies below the reference's best at its position (0 where it is the
    reference's own first choice). With `control` ("float8" or
    "state_bfloat16") the gap of the token that the control puts first
    instead."""
    import jax.numpy as jnp

    p, n = len(rec["prompt"]), len(rec["tokens"])
    pad = next(x for x in sorted(mix["check_pad"]) if x >= p + n - 1)
    ids = np.zeros(pad, np.int32)
    ids[:p + n - 1] = np.concatenate([rec["prompt"], rec["tokens"][:-1]])
    rows = np.arange(p - 1, p - 1 + n)
    ref = olmo_hybrid_ref.served_logits(w, ids, rows, model)
    if control is None:
        chosen = jnp.asarray(np.asarray(rec["tokens"], np.int32))
    else:
        chosen = jnp.argmax(olmo_hybrid_ref.served_logits(
            w, ids, rows, model, quantized=control == "float8",
            state_dtype="bfloat16" if control == "state_bfloat16" else None),
            axis=-1)
    picked = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - picked)


def compare(model, mix, w, sample, control=None):
    """(`token_gap`: the worst over the sample's served tokens, tokens
    compared); `w` the seed's weights."""
    gaps = [token_gaps(model, mix, w, rec, control) for rec in sample]
    if not gaps:
        return np.inf, 0
    gaps = np.concatenate(gaps)
    print(f"[serve_lin] {control or 'served'} tokens against the reference: "
          f"{len(gaps)} compared, {int(np.sum(gaps > 0))} not its first "
          f"choice, worst {gaps.max():.5f}, mean {gaps.mean():.6f}",
          flush=True)
    return float(gaps.max()), len(gaps)


def check(cell, w, seed, finished, builds, control=None):
    """The cell's `Checks` over the finished requests of a window; `w` the
    seed's weights, made again once the engine's are freed."""
    model, mix = cell["model"], cell["mix"]
    checks = harness.Checks(cell["limits"])
    sample = serve.pick_sample(finished, mix["check_requests"], seed)
    t0 = time.perf_counter()
    gap, compared = compare(model, mix, w, sample, control)
    checks.add("token_gap", gap)
    checks.add("tokens_compared", compared, 1, at_most=False)
    checks.add("short_answers", sum(len(r["tokens"]) != r["max_new"]
                                    for r in finished), 0)
    checks.add("compiles_in_window", builds, 0)
    print(f"[serve_lin] reference over {len(sample)} of {len(finished)} "
          f"finished requests (a seeded sample of check_requests "
          f"{mix['check_requests']}, the longest among them), {compared} "
          f"served tokens in {time.perf_counter() - t0:.1f} s", flush=True)
    return checks


def run(cell, args, tools):
    import jax

    from paddle_tpu.models.gpt import GPTConfig

    model, mix = cell["model"], cell["mix"]
    counter, phase = tools["counter"], tools["phase"]
    # a program that lacks a key of this configuration stops here, before
    # 8 GB of weights are drawn for it
    GPTConfig(**model)
    reqs = generate.requests(mix, model["vocab_size"], args.seed)
    w = weights_olmo_hybrid.make(model, args.seed, mix["weights_dtype"])
    phase("requests and weights made")
    eng, pool = build_server(cell, w)
    del w
    phase("model, engine and pool built")
    t0 = time.perf_counter()
    eng.warmup()
    phase("engine.warmup() done")
    print(f"[serve_lin] warmup() {time.perf_counter() - t0:.1f} s, "
          f"{eng.stats()['compiles']}", flush=True)
    serve.warm_traffic(cell, eng, pool, args.seed, tools["spans"])
    print(f"[serve_lin] warm traffic done; executables built so far "
          f"{counter.builds} ({counter.hits} from the persistent cache)",
          flush=True)

    sampler = CacheSampler(eng, mix["stats_every_s"])
    opened = tools["window_opened"]

    def window_opened(t):
        opened(t)
        sampler.start()

    win = serve.window(cell, eng, pool, reqs, args.seconds,
                       {**tools, "window_opened": window_opened}, args.seed)
    records, t_close, builds = win["records"], win["t_close"], win["builds"]
    samples = sampler.stop(t_close)
    device, reserved = harness.device_info(jax.devices(), cell["chips"])
    print(f"[serve_lin] memory_stats {jax.devices()[0].memory_stats()}",
          flush=True)

    # ---- the end-to-end numbers, over all requests and all tokens
    timeout_ms = mix["request_timeout_s"] * 1e3
    ttft = [(r["token_t"][0] - r["t_submit"]) * 1e3
            if r["token_t"] else timeout_ms for r in records]
    itl, delivered = [], 0
    for r in records:
        ts = np.asarray(r["token_t"])
        delivered += int(np.sum(ts <= t_close))
        itl += list(np.diff(ts)[ts[1:] <= t_close] * 1e3)
    failed = [r for r in records if r["status"] == "failed"]
    finished = [r for r in records if r["status"] == "completed"]
    a, b = win["snaps"]["open"], win["snaps"]["close"]
    print(f"[serve_lin] window {args.seconds} s: {len(records)} requests "
          f"sent, {len(finished)} finished, {len(failed)} failed, "
          f"{delivered} tokens, {len(itl)} gaps, {builds} executable "
          f"build(s) inside it; first failure: "
          f"{failed[0].get('error') if failed else None}", flush=True)
    q = np.percentile(itl, [50, 90, 95, 99]).round(2).tolist() if itl else []
    print(f"[serve_lin] time to first token over {len(ttft)} requests: mean "
          f"{np.mean(ttft):.1f} ms, p90 {np.percentile(ttft, 90):.1f} ms; "
          f"gaps p50/p90/p95/p99 {q} ms, longest "
          f"{np.sort(itl)[-3:][::-1].round(1).tolist()} ms; in the window: "
          + ", ".join(f"{k} {b[k] - a[k]}" for k in (
              "steps", "prefill_chunks", "lin_chunk_tokens",
              "lin_step_tokens", "wedged_steps", "isolation_rounds",
              "timed_out"))
          + f"; state slots at most {b['lin_state_slots_peak']}, "
            f"{len(samples)} cache samples", flush=True)

    # ---- shut the program down and free it, then the reference
    free_server(eng, pool)
    del eng, pool
    w = weights_olmo_hybrid.make(model, args.seed, mix["weights_dtype"])
    checks = check(cell, w, args.seed, finished, builds)
    if finished:
        # the two controls in the program's place on the longest request:
        # printed, not compared (the limit lies between `served` above and
        # `float8` here; calibrate_lin.py reads them over the whole sample)
        longest = serve.pick_sample(finished, 1, args.seed)
        for control in ("float8", "state_bfloat16"):
            compare(model, mix, w, longest, control)
    del w
    return {
        "attempted": len(records), "failed": len(failed), "checks": checks,
        "device": device,
        "end_to_end": {
            "serve_tokens_per_s": delivered / args.seconds,
            "serve_itl_p95_ms": float(np.percentile(itl, 95))
            if itl else timeout_ms},
        "counters": {"snaps": win["snaps"], "records": records,
                     "cache_samples": samples,
                     "reserved_peak_bytes": reserved,
                     "t_open": win["t_open"], "t_close": t_close,
                     "window_s": args.seconds, "requests": len(records),
                     "finished": len(finished), "gaps": len(itl)},
    }


def trace_scope(ctx):
    """`serve.trace_scope`, and the log's `[spans]` line (host self time by
    scheduler phase, the device's idle seconds by phase), which this cell
    lists no reader for."""
    scope = serve.trace_scope(ctx)
    if scope:
        program_spans.report({**ctx, "scope": scope})
    return scope
