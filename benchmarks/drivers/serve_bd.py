"""Serving cells of a model that answers by diffusion over blocks:
`ServingPool(decode_engine=DecodeEngine(..., block_diffusion=...))`.

The window, the clients and the warm traffic are `drivers/serve.py`'s (a
private copy of that module is given this file's `serve_one`, which keeps
each token's denoising pass beside it, and `engine_counters`, which adds the
block and expert counters to every snapshot). What differs is the server
(`build_server`: the block option, weights drawn a layer at a time into a
model built under `LazyGuard`) and the comparison that decides `correct`.

The comparison, on what the timed window served: for a seeded sample of the
finished requests, the longest among them, a seeded sample of (block, pass)
pairs weighted to late blocks. The reference forwards `prompt + committed
blocks + the block as it stood before that pass`, rebuilt from the tokens
and their passes, under the block mask. `token_gap`: how far the reference's
logit of a served token lies under the reference's best at that position,
worst over the positions that pass fixed. `pick_gap`: how far the
reference's confidence (log-probability of its best token) at a position
the engine fixed lies under the largest at a position it left masked in
that pass, 0 where it does not. Each is the MEAN over the sampled passes
(`pick_gap`: over those that left a position masked); the worst pass is in
the log. With seeded random weights the best logits of a position, and the
confidences of a block, lie close together, so a sound bfloat16 run flips a
near-tie now and then and its worst pass reads within 2-3 x of float8's or
of a wrong rule's worst; the means differ by 4 x and more. Both see the cache of every earlier block, so prefill and commits
are covered. `short_blocks`: a fully delivered block had fewer denoising
passes than its generated positions need.
"""
from __future__ import annotations

import gc
import os
import time

import numpy as np

from benchmarks import harness, program_spans, weights_sdar
from benchmarks.reference import sdar_ref
from benchmarks.traffic import generate

serve = harness.load_module(
    os.path.join(harness.HERE, "drivers", "serve.py"), "driver_serve_for_bd")


def build_server(cell, w):
    """The program's server for this cell, holding the benchmark's weights.
    The model is built lazily: its own float32 initial values (17 GB at the
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
        raise SystemExit(f"[serve_bd] weights and model differ in "
                         f"{sorted(names ^ set(w))[:6]}")
    for n, p in net.named_parameters():
        p._value = w[n]
    eng = DecodeEngine(net, default_timeout=mix["request_timeout_s"],
                       block_diffusion=mix["block_diffusion"], **geo)
    pool = ServingPool(decode_engine=eng,
                       default_timeout=mix["request_timeout_s"])
    return eng, pool


def free_server(eng, pool):
    """Shut the program down and give its device memory back: the weights
    (9 GB at the served size) and the pool are deleted buffer by buffer,
    whoever still refers to the engine (the window's trace hooks, compiled
    steps' closures), so that the reference's copy of the weights fits."""
    pool.shutdown()
    eng.shutdown()
    held = [p._value for _, p in eng.model.named_parameters()]
    held += [t for entry in eng.pool.tensors for t in entry]
    for a in held:
        a.delete()
    gc.collect()


def requests(mix, vocab_size, seed):
    """The generator's requests; a prompt id that equals the mask token is
    drawn again (the mask marks what is still to be generated)."""
    mask = mix["block_diffusion"]["mask_token_id"]
    rng = generate.rng_for(seed, 6)
    reqs = generate.requests(mix, vocab_size, seed)
    for r in reqs:
        hit = r["prompt"] == mask
        while hit.any():
            r["prompt"][hit] = rng.integers(1, vocab_size, int(hit.sum()),
                                            dtype=np.int32)
            hit = r["prompt"] == mask
    return reqs


def serve_one(pool, req, rec, spans, t_cancel=None):
    """`serve.serve_one`, and the denoising pass of every token kept."""
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
        n = len(rec["token_t"])
        rec["tokens"] = list(stream.tokens)[:n]
        rec["passes"] = list(stream.passes)[:n]
    except Exception as e:  # noqa: BLE001 - a failed request is counted,
        rec["status"] = "failed"          # with its error, not raised
        rec["error"] = f"{type(e).__name__}: {e}"[:300]
    rec["t_end"] = time.perf_counter()


BD_COUNTERS = ("bd_forwards", "bd_commit_forwards", "bd_tokens_fixed",
               "bd_blocks_committed", "bd_head_dispatches",
               "bd_context_tokens", "moe_distinct_experts",
               "moe_load_max_over_mean_sum", "moe_layer_dispatches")


def engine_counters(eng):
    st = eng.stats()
    out = {k: st[k] for k in ("steps", "prefills", "prefill_chunks",
                              "tokens_out", "completed", "failed",
                              "timed_out", "wedged_steps",
                              "isolation_rounds", "compiles",
                              "step_active", "step_slots")}
    out["prefix_hits"] = st["prefix_cache"]["hits"]
    out.update({k: st.get(k, 0) for k in BD_COUNTERS})
    return out


serve.serve_one = serve_one
serve.engine_counters = engine_counters


# ---- the comparison ------------------------------------------------------

def blocks_of(rec, bl):
    """The fully delivered generated blocks of a finished request: dicts of
    `start` (cache position), `given` (leading positions that are the
    prompt's), `tokens` and `passes` (length bl; 0 for a given position)."""
    p, n = len(rec["prompt"]), len(rec["tokens"])
    aligned = p // bl * bl
    seq = np.concatenate([rec["prompt"], rec["tokens"]]).astype(np.int32)
    passes = np.concatenate([np.zeros(p, np.int32), rec["passes"]])
    out = []
    for start in range(aligned, p + n - bl + 1, bl):
        out.append({"start": start, "given": max(0, p - start),
                    "tokens": seq[start:start + bl],
                    "passes": passes[start:start + bl]})
    return out


def short_blocks(finished, bd):
    """Blocks committed after fewer denoising passes than their generated
    positions need at `block_length / denoising_steps` a pass."""
    bl, per = bd["block_length"], bd["block_length"] // bd["denoising_steps"]
    short = 0
    for rec in finished:
        for b in blocks_of(rec, bl):
            need = -(-(bl - b["given"]) // per)
            short += int(b["passes"].max()) < need
    return short


def pick_pairs(rec, bd, n, rng):
    """`n` (block, pass) pairs of one request, late blocks more likely."""
    blocks = blocks_of(rec, bd["block_length"])
    pairs = [(b, t) for b in blocks
             for t in range(1, int(b["passes"].max()) + 1)]
    if not pairs:
        return []
    weight = np.array([b["start"] for b, _ in pairs], float)
    weight = weight - weight.min() + bd["block_length"]
    take = rng.choice(len(pairs), size=min(n, len(pairs)), replace=False,
                      p=weight / weight.sum())
    return [pairs[i] for i in take]


def compare(model, mix, seed, sample, quantized=False):
    """(`token_gap`, `pick_gap`, positions compared) of the sampled
    requests against the float32 reference, each gap the mean over the
    sampled passes (inf where there was none: nothing was compared). With `quantized`, the float8
    control is put in the program's place: the token it would serve and the
    positions it would fix, from the same state."""
    import jax
    import jax.numpy as jnp

    bd = mix["block_diffusion"]
    bl, mask = bd["block_length"], bd["mask_token_id"]
    per = bl // bd["denoising_steps"]
    pads = sorted(mix["check_pad"])
    w = weights_sdar.make(model, seed, mix["weights_dtype"])
    logp = jax.jit(lambda lg: jax.nn.log_softmax(lg, axis=-1))
    rng = generate.rng_for(seed, 7)
    token_gaps, pick_gaps, compared = [], [], 0
    for rec in sample:
        pairs = pick_pairs(rec, bd, mix["check_pairs"], rng)
        if not pairs:
            continue
        prior = np.concatenate([rec["prompt"],
                                rec["tokens"]]).astype(np.int32)
        longest = max(b["start"] for b, _ in pairs) + bl
        pad = next(p for p in pads if p >= longest)
        ids = np.zeros((len(pairs), pad), np.int32)
        rows = np.zeros((len(pairs), bl), np.int32)
        for i, (b, t) in enumerate(pairs):
            state = np.where((b["passes"] < t), b["tokens"], mask)
            ids[i, :b["start"]] = prior[:b["start"]]
            ids[i, b["start"]:b["start"] + bl] = state
            rows[i] = np.arange(b["start"], b["start"] + bl)
        ref = logp(sdar_ref.served_logits(w, ids, rows, model))
        ref = np.asarray(ref)                       # [pairs, bl, vocab]
        ctl = np.asarray(logp(sdar_ref.served_logits(
            w, ids, rows, model, quantized=True))) if quantized else None
        for i, (b, t) in enumerate(pairs):
            masked = b["passes"] >= t               # before this pass
            best = ref[i].max(-1)
            if quantized:
                # what the control would have done from this state
                fix = sdar_ref.pick(np.exp(ctl[i].max(-1)), masked, per)
                served = ctl[i].argmax(-1)
            else:
                fix = np.flatnonzero(b["passes"] == t)
                served = b["tokens"]
            left = np.setdiff1d(np.flatnonzero(masked), fix)
            token_gaps.append(float(np.max(
                best[fix] - ref[i][fix, served[fix]])))
            if len(left):
                pick_gaps.append(max(0.0, float(
                    best[left].max() - best[fix].min())))
            compared += len(fix)
    for name, gaps in (("token", token_gaps), ("pick", pick_gaps)):
        if gaps:
            print(f"[serve_bd] {name} gaps over {len(gaps)} passes: mean "
                  f"{np.mean(gaps):.5f}, worst {max(gaps):.5f}, "
                  f"{sum(g > 0 for g in gaps)} over 0", flush=True)
    return (float(np.mean(token_gaps)) if token_gaps else np.inf,
            float(np.mean(pick_gaps)) if pick_gaps else np.inf, compared)


def check(cell, seed, finished, builds, quantized=False):
    """The cell's `Checks` over the finished requests of a window."""
    model, mix = cell["model"], cell["mix"]
    checks = harness.Checks(cell["limits"])
    sample = serve.pick_sample(finished, mix["check_requests"], seed)
    t0 = time.perf_counter()
    token_gap, pick_gap, compared = compare(model, mix, seed, sample,
                                            quantized) \
        if sample else (np.inf, np.inf, 0)
    checks.add("token_gap", token_gap)
    checks.add("pick_gap", pick_gap)
    checks.add("positions_compared", compared, 1, at_most=False)
    checks.add("short_answers", sum(
        len(r["tokens"]) != r["max_new"] or len(r["passes"]) != r["max_new"]
        for r in finished), 0)
    checks.add("short_blocks", short_blocks(finished,
                                            mix["block_diffusion"]), 0)
    checks.add("compiles_in_window", builds, 0)
    print(f"[serve_bd] reference over {len(sample)} of {len(finished)} "
          f"finished requests (a seeded sample of check_requests "
          f"{mix['check_requests']}, the longest among them), "
          f"{mix['check_pairs']} (block, pass) pairs each, {compared} "
          f"positions in {time.perf_counter() - t0:.1f} s", flush=True)
    return checks


def run(cell, args, tools):
    import jax

    from paddle_tpu.models.gpt import GPTConfig

    model, mix = cell["model"], cell["mix"]
    counter, phase = tools["counter"], tools["phase"]
    # a program that lacks a key of this configuration stops here, before
    # 9 GB of weights are drawn for it
    GPTConfig(**model)
    reqs = requests(mix, model["vocab_size"], args.seed)
    w = weights_sdar.make(model, args.seed, mix["weights_dtype"])
    phase("requests and weights made")
    eng, pool = build_server(cell, w)
    del w
    phase("model, engine and pool built")
    t0 = time.perf_counter()
    eng.warmup()
    phase("engine.warmup() done")
    print(f"[serve_bd] warmup() {time.perf_counter() - t0:.1f} s, "
          f"{eng.stats()['compiles']}", flush=True)
    serve.warm_traffic(cell, eng, pool, args.seed, tools["spans"])
    print(f"[serve_bd] warm traffic done; executables built so far "
          f"{counter.builds} ({counter.hits} from the persistent cache)",
          flush=True)

    win = serve.window(cell, eng, pool, reqs, args.seconds, tools,
                       args.seed)
    records, t_close, builds = win["records"], win["t_close"], win["builds"]
    device, reserved = harness.device_info(jax.devices(), cell["chips"])
    print(f"[serve_bd] memory_stats {jax.devices()[0].memory_stats()}",
          flush=True)

    # ---- the end-to-end numbers, over all requests and all tokens. A
    # client sees a block's tokens at once: the gaps inside a block are ~0
    # and the tail of all gaps is the time of a block
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
    print(f"[serve_bd] window {args.seconds} s: {len(records)} requests "
          f"sent, {len(finished)} finished, {len(failed)} failed, "
          f"{delivered} tokens, {len(itl)} gaps, {builds} executable "
          f"build(s) inside it; first failure: "
          f"{failed[0].get('error') if failed else None}", flush=True)
    print(f"[serve_bd] time to first block over {len(ttft)} requests: mean "
          f"{np.mean(ttft):.1f} ms, p90 {np.percentile(ttft, 90):.1f} ms; "
          f"longest gaps {np.sort(itl)[-3:][::-1].round(1).tolist()} ms; "
          f"in the window: " + ", ".join(
              f"{k} {b[k] - a[k]}" for k in (
                  "steps", "prefill_chunks", "bd_forwards",
                  "bd_commit_forwards", "bd_tokens_fixed",
                  "bd_blocks_committed", "moe_distinct_experts",
                  "wedged_steps", "isolation_rounds", "timed_out")),
          flush=True)

    # ---- shut the program down and free it, then the reference
    free_server(eng, pool)
    del eng, pool
    checks = check(cell, args.seed, finished, builds)
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
    """`serve.trace_scope`, and the log's `[spans]` line (host self time by
    scheduler phase, the device's idle seconds by phase): in the dense
    serving cell a reader of the program's spans writes it, and this cell
    lists none of those (PERF.md section 7)."""
    scope = serve.trace_scope(ctx)
    if scope:
        program_spans.report({**ctx, "scope": scope})
    return scope
