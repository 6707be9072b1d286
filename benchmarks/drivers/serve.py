"""Serving cells: `ServingPool(decode_engine=DecodeEngine(...))`, requests
sent through `submit_generate` and tokens streamed to client threads.

Closed loop: each client sends its next request when its last one has ended.
The window lasts `--seconds`; what is still in flight at its close is
cancelled after its first token. Once the engine is shut down and freed, the
plain reference runs once over a seeded sample of the finished requests, the
longest among them, and the served tokens are held against its logits.
"""
from __future__ import annotations

import gc
import threading
import time

import numpy as np

from benchmarks import harness, weights
from benchmarks.reference import gpt_ref
from benchmarks.traffic import generate

def build_server(cell, w):
    """The program's server for this cell, holding the benchmark's
    weights."""
    from paddle_tpu.inference import DecodeEngine, ServingPool
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    mix = cell["mix"]
    geo = dict(mix["engine"])
    for key in ("decode_buckets", "prefill_buckets"):
        geo[key] = tuple(geo[key])
    net = GPTForCausalLM(GPTConfig(**cell["model"]))
    net.eval()
    for n, p in net.named_parameters():
        p._value = w[n]
    eng = DecodeEngine(net, default_timeout=mix["request_timeout_s"], **geo)
    pool = ServingPool(decode_engine=eng,
                       default_timeout=mix["request_timeout_s"])
    return eng, pool


def warm_requests(mix, vocab_size, seed):
    """A few requests that walk every executable the window will use: each
    prefill bucket up to the chunk, a prompt that splits into chunks, and
    output lengths that differ, so that the batch shrinks through every
    decode bucket."""
    geo = mix["engine"]
    clients = mix["arrival"].get("clients", max(geo["decode_buckets"]))
    chunk = geo["prefill_chunk"]
    small = [b for b in geo["prefill_buckets"] if b <= chunk]
    lens = small + [chunk + small[0]]
    rng = generate.rng_for(seed, 4)
    return [{"prompt": rng.integers(1, vocab_size, lens[i % len(lens)],
                                    dtype=np.int32),
             "max_new": 2 + i} for i in range(max(clients, len(lens)))]


def serve_one(pool, req, rec, spans, t_cancel=None):
    """One request through the pool; the time of every token as the client
    sees it. After `t_cancel` the request is cancelled once it has its
    first token."""
    rec["t_submit"] = time.perf_counter()
    try:
        with spans.span("submit"):
            stream = pool.submit_generate(req["prompt"], req["max_new"])
        for _ in stream:
            t = time.perf_counter()
            rec["token_t"].append(t)
            if t_cancel is not None and t >= t_cancel:
                stream.cancel()
                rec["status"] = "cancelled_at_close"
                break
        else:
            rec["status"] = "completed"
        rec["tokens"] = list(stream.tokens)[:len(rec["token_t"])]
    except Exception as e:  # noqa: BLE001 - a failed request is counted,
        rec["status"] = "failed"          # with its error, not raised
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    rec["t_end"] = time.perf_counter()


def engine_counters(eng):
    st = eng.stats()
    out = {k: st[k] for k in ("steps", "prefills", "prefill_chunks",
                              "tokens_out", "completed", "failed",
                              "timed_out", "wedged_steps",
                              "isolation_rounds", "compiles")}
    out["prefix_hits"] = st["prefix_cache"]["hits"]
    out["step_active"] = getattr(eng, "_step_active", None)
    out["step_slots"] = getattr(eng, "_step_slots", None)
    return out


def token_gaps(model, seed, dtype, sample, pad_to, quantized=False):
    """For every served token of the sampled requests: how far its logit
    lies below the reference's best at its position (0 where it is the
    reference's own first choice). With `quantized`, the gap of the token
    that the lower-precision control puts first instead. Sequences and
    rows are padded to `pad_to`, so one compiled program serves them all."""
    import jax
    import jax.numpy as jnp

    @jax.jit
    def gap_of(ref, chosen):
        picked = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
        return jnp.max(ref, axis=-1) - picked

    w = weights.make(model, seed, dtype)
    gaps = []
    for rec in sample:
        p, n = len(rec["prompt"]), len(rec["tokens"])
        ids = np.zeros(pad_to, np.int32)
        seq = np.concatenate([rec["prompt"],
                              rec["tokens"][:-1]]).astype(np.int32)
        ids[:len(seq)] = seq
        rows = np.zeros(pad_to, np.int32)
        rows[:n] = np.arange(p - 1, p - 1 + n)
        ref = gpt_ref.served_logits(w, ids, rows, model)
        if quantized:
            chosen = jnp.argmax(gpt_ref.served_logits(
                w, ids, rows, model, quantized=True), axis=-1)
        else:
            chosen = np.zeros(pad_to, np.int32)
            chosen[:n] = rec["tokens"]
        gaps.append(np.asarray(gap_of(ref, jnp.asarray(chosen)))[:n])
    return np.concatenate(gaps)


def pick_sample(finished, n, seed):
    """At most `n` finished requests drawn from the seed, the longest among
    them. The mix asks for more than a window finishes, so every finished
    request is compared: the widest gap over a few hundred tokens let the
    float8 control through on one seed in 18 (PERF.md section 2)."""
    if not finished:
        return []
    longest = max(finished, key=lambda r: len(r["prompt"]) + len(r["tokens"]))
    rest = [r for r in finished if r is not longest]
    order = generate.rng_for(seed, 5).permutation(len(rest))
    return [longest] + [rest[i] for i in order[:max(0, n - 1)]]


def drive(work):
    threads = [threading.Thread(target=w, name=f"client-{i}")
               for i, w in enumerate(work)]
    for t in threads:
        t.start()
    return threads


def warm_traffic(cell, eng, pool, seed, spans):
    """Every executable really runs once before the window."""
    warm = warm_requests(cell["mix"], cell["model"]["vocab_size"], seed)
    recs = [{"token_t": []} for _ in warm]
    for t in drive([lambda r=r, rec=rec: serve_one(pool, r, rec, spans)
                    for r, rec in zip(warm, recs)]):
        t.join()
    bad = [r for r in recs if r["status"] != "completed"]
    if bad:
        raise SystemExit(f"[serve] warm traffic failed: {bad[:2]}")


def window(cell, eng, pool, reqs, seconds, tools, seed=0):
    """The measured window: `clients` closed-loop clients, or an open-loop
    schedule drawn from `seed`, for `seconds`."""
    mix = cell["mix"]
    spans, counter, tracer = tools["spans"], tools["counter"], tools["tracer"]
    records, lock, nxt = [], threading.Lock(), [0]
    builds0 = counter.builds
    snaps = {}
    tracer.on_start = lambda: snaps.__setitem__("trace0",
                                                engine_counters(eng))
    tracer.on_stop = lambda: snaps.__setitem__("trace1",
                                               engine_counters(eng))
    t_open = time.perf_counter()
    t_close = t_open + seconds
    tools["window_opened"](t_open)
    snaps["open"] = engine_counters(eng)

    def send(i, t_due=None):
        req = reqs[i % len(reqs)]
        rec = {"i": i, "prompt": req["prompt"], "max_new": req["max_new"],
               "token_t": [], "t_due": t_due}
        records.append(rec)
        serve_one(pool, req, rec, spans, t_cancel=t_close)

    def client():
        # closed loop: the next request when the last one has ended
        while time.perf_counter() < t_close:
            with lock:
                i = nxt[0]
                nxt[0] += 1
            send(i)

    def schedule():
        # open loop: every request leaves when it is due, whatever the
        # server does; each is timed from when it was due
        due = generate.open_loop_schedule(mix["arrival"], seconds, seed)
        started = []
        for i, d in enumerate(due):
            time.sleep(max(0.0, t_open + d - time.perf_counter()))
            started += drive([lambda i=i, d=d: send(i, t_open + d)])
        for t in started:
            t.join(mix["request_timeout_s"] + 60.0)

    threads = drive([client] * mix["arrival"]["clients"]
                    if mix["arrival"]["kind"] == "closed" else [schedule])
    while time.perf_counter() < t_close:
        tracer.poll(time.perf_counter() - t_open)
        time.sleep(0.05)
    snaps["close"] = engine_counters(eng)
    tracer.stop()
    for t in threads:
        t.join(mix["request_timeout_s"] + 60.0)
    return {"records": records, "snaps": snaps, "t_open": t_open,
            "t_close": t_close, "builds": counter.builds - builds0}


def run(cell, args, tools):
    import jax

    model, mix = cell["model"], cell["mix"]
    counter = tools["counter"]
    phase = tools["phase"]
    reqs = generate.requests(mix, model["vocab_size"], args.seed)
    w = weights.make(model, args.seed, mix["weights_dtype"])
    phase("requests and weights made")
    eng, pool = build_server(cell, w)
    del w
    phase("model, engine and pool built")
    t0 = time.perf_counter()
    eng.warmup()
    phase("engine.warmup() done")
    print(f"[serve] warmup() {time.perf_counter() - t0:.1f} s, "
          f"{eng.stats()['compiles']}", flush=True)
    warm_traffic(cell, eng, pool, args.seed, tools["spans"])
    print(f"[serve] warm traffic done; executables built so far "
          f"{counter.builds} ({counter.hits} from the persistent cache)",
          flush=True)

    win = window(cell, eng, pool, reqs, args.seconds, tools, args.seed)
    records, t_close, builds = win["records"], win["t_close"], win["builds"]
    device, reserved = harness.device_info(jax.devices(), cell["chips"])
    print(f"[serve] memory_stats {jax.devices()[0].memory_stats()}",
          flush=True)

    # ---- the end-to-end numbers, over all requests and all tokens
    timeout_ms = mix["request_timeout_s"] * 1e3
    ttft = [(r["token_t"][0] - (r["t_due"] or r["t_submit"])) * 1e3
            if r["token_t"] else timeout_ms for r in records]
    late = [r["t_submit"] - r["t_due"] for r in records if r["t_due"]]
    if late:
        print(f"[serve] open loop: the generator ran "
              f"{np.median(late) * 1e3:.2f} ms late at the median, "
              f"{max(late) * 1e3:.2f} ms at most", flush=True)
    itl, delivered = [], 0
    for r in records:
        ts = np.asarray(r["token_t"])
        delivered += int(np.sum(ts <= t_close))
        itl += list(np.diff(ts)[ts[1:] <= t_close] * 1e3)
    failed = [r for r in records if r["status"] == "failed"]
    finished = [r for r in records if r["status"] == "completed"]
    short = [r for r in finished if len(r["tokens"]) != r["max_new"]]
    print(f"[serve] window {args.seconds} s: {len(records)} requests sent, "
          f"{len(finished)} finished, {len(failed)} failed, {delivered} "
          f"tokens, {len(itl)} gaps, {builds} executable build(s) inside it; "
          f"first failure: {failed[0].get('error') if failed else None}",
          flush=True)
    # time to first token is no metric of this cell (some thirty requests a
    # window: its tail is the seed's draw); the log keeps it
    print(f"[serve] time to first token over {len(ttft)} requests: mean "
          f"{np.mean(ttft):.1f} ms, p90 {np.percentile(ttft, 90):.1f} ms",
          flush=True)
    # what explains a run that delivers less at an unchanged tail: a stall
    # shows as a few very long gaps, a wedged or retried step in the counters
    a, b = win["snaps"]["open"], win["snaps"]["close"]
    print(f"[serve] longest gaps {np.sort(itl)[-3:][::-1].round(1).tolist()} "
          f"ms; in the window: " + ", ".join(
              f"{k} {b[k] - a[k]}" for k in ("steps", "prefill_chunks",
                                             "wedged_steps",
                                             "isolation_rounds",
                                             "timed_out")), flush=True)

    # ---- shut the program down and free it, then the reference
    pool.shutdown()
    eng.shutdown()
    del eng, pool
    gc.collect()
    checks = harness.Checks(cell["limits"])
    t0 = time.perf_counter()
    sample = pick_sample(finished, mix["check_requests"], args.seed)
    gaps = token_gaps(model, args.seed, mix["weights_dtype"], sample,
                      mix["engine"]["max_length"]) if sample \
        else np.array([np.inf])
    checks.add("token_gap", float(np.max(gaps)))
    checks.add("tokens_compared", len(gaps), 1, at_most=False)
    checks.add("short_answers", len(short), 0)
    checks.add("compiles_in_window", builds, 0)
    print(f"[serve] reference over {len(sample)} requests, {len(gaps)} "
          f"served tokens in {time.perf_counter() - t0:.1f} s: "
          f"{int(np.sum(gaps > 0))} not the reference's first choice",
          flush=True)
    return {
        "attempted": len(records), "failed": len(failed), "checks": checks,
        "device": device,
        "end_to_end": {
            "serve_tokens_per_s": delivered / args.seconds,
            "serve_itl_p95_ms": float(np.percentile(itl, 95))
            if itl else timeout_ms},
        "counters": {"snaps": win["snaps"], "records": records,
                     "reserved_peak_bytes": reserved,
                     "t_open": win["t_open"], "t_close": t_close,
                     "window_s": args.seconds, "requests": len(records),
                     "finished": len(finished), "gaps": len(itl)},
    }


def trace_scope(ctx):
    """The traced stretch the per-layer readers share: from the first device
    operation of the trace to the last, with the device time of each
    launched program by name and of the decode step's programs, which the
    mix names (`trace_names.decode`: `jit_step` is the decode step of every
    bucket; `jit_prefill` is a prompt chunk). A trace in which none of the
    named programs ran is an error, not a silent metric."""
    from benchmarks import trace_reader as tr

    trace = ctx["trace"]
    scope = tr.reduce_window(trace, ignore=("client_wait",))
    if not scope:
        return {}
    mods = tr.within(trace["devices"][0]["modules"], scope["lo"],
                     scope["hi"])
    scope["module_s"] = {n: ns / 1e9 for n, ns in
                         tr.totals(mods, self_time=False).items()}
    wanted = ctx["mix"]["trace_names"]["decode"]
    if not any(n in scope["module_s"] for n in wanted):
        raise RuntimeError(f"none of the decode programs {wanted} in the "
                           f"trace; it has {sorted(scope['module_s'])}")
    scope["decode_s"] = sum(scope["module_s"].get(n, 0.0) for n in wanted)
    # the requests' tokens that the client saw inside the traced stretch,
    # by the host's clock: (decode positions, prompts finished)
    t0, t1 = ctx["tracer"].t_start, ctx["tracer"].t_stop
    decode_pos, prompts = [], []
    for r in ctx["counters"]["records"]:
        p = len(r["prompt"])
        for j, t in enumerate(r["token_t"]):
            if t0 <= t <= t1:
                if j == 0:
                    prompts.append(p)
                else:
                    decode_pos.append(p + j - 1)
    scope["decode_positions"] = decode_pos
    scope["prompts_finished"] = prompts
    return scope
