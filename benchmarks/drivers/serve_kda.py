"""Serving cells of the Ling-flash configuration as one chip's share of its
deployment: per-channel delta-rule layers (a state slot a sequence) beside
latent attention (one compressed row a token in the paged pool), and expert
layers that hold some of the router's experts.

The window, the clients, the warm traffic and the sample of finished
requests are `drivers/serve.py`'s, and the server is built and freed as
`drivers/serve_lin.py` does (this file's private copy of that module, and
through it of `serve.py`, is given this file's `engine_counters`, which adds
the state slots', the latent rows' and the expert layers' counters to every
snapshot). What differs is the weights, the sampler of `stats()` through the
window, and the reference the served tokens are held against.

The comparison, on what the timed window served: a seeded sample of the
finished requests, the longest among them, each forwarded whole through the
plain reference given the same share (one sequence at a time, the recurrence
position by position, the experts a loop over a token's choices, latent
attention through the expanded heads). `token_gap`, as the dense cell
defines it: how far the reference's logit of a served token lies under the
reference's best at its position, the worst over every served token of the
sample, and `token_gap_mean`, its mean over them: the worst gap is what a
lower precision or a wrong decay moves, the mean what a router that chooses
by another rule moves (it shifts many tokens a little, where rounding flips
a few near-ties). The log also holds, on the longest request, the readings of the
controls put in the program's place (the token each puts first): the float8
reference, which `token_gap`'s limit has to refuse, and the two planted
faults of `ling_ref` (a head's decay as its mean over the key channels; the
router without its selection bias); and the share of expert choices that
fell on experts held here, as the engine counted it over the window and as
the reference counts it over the sample.
"""
from __future__ import annotations

import os
import time

import numpy as np

from benchmarks import harness, weights_ling
from benchmarks.reference import ling_ref
from benchmarks.traffic import generate

lin = harness.load_module(
    os.path.join(harness.HERE, "drivers", "serve_lin.py"),
    "driver_serve_lin_for_kda")
serve = lin.serve

#: counters beyond the dense cell's that every snapshot of the engine holds
#: (numbers only: a reader takes the difference of two snapshots)
MORE_COUNTERS = lin.LIN_COUNTERS + (
    "mla_rows_in_use", "mla_row_bytes", "moe_experts_held",
    "moe_choices_total", "moe_choices_local", "moe_distinct_experts",
    "moe_chunk_distinct_experts", "moe_expert_reads",
    "moe_load_max_over_mean_sum", "moe_layer_dispatches")
SAMPLED = ("lin_state_slots", "lin_state_bytes", "kv_blocks_in_use",
           "mla_rows_in_use", "mla_row_bytes")
CONTROLS = {"float8": {"quantized": True},
            "mean_decay": {"fault": "mean_decay"},
            "no_bias": {"fault": "no_bias"},
            "no_groups": {"fault": "no_groups"}}


def engine_counters(eng):
    st = eng.stats()
    out = {k: st[k] for k in ("steps", "prefills", "prefill_chunks",
                              "tokens_out", "completed", "failed",
                              "timed_out", "wedged_steps",
                              "isolation_rounds", "compiles",
                              "step_active", "step_slots",
                              "donated_dispatches", "pool_rebuilds")}
    out["prefix_hits"] = st["prefix_cache"]["hits"]
    # before the first expert layer has run the engine has no count yet
    out.update({k: st.get(k, 0) for k in MORE_COUNTERS})
    return out


serve.engine_counters = engine_counters


class StatsSampler(lin.CacheSampler):
    """`serve_lin`'s sampler of `stats()` through the window, reading the
    state slots and the latent rows in use beside the sequences resident."""

    def _run(self):
        while not self._stop.wait(self.every_s):
            st = self.eng.stats()
            self.samples.append({
                "t": time.perf_counter(),
                "resident": st["active"] + st["prefilling"],
                **{k: st[k] for k in SAMPLED}})


# ---- the comparison ------------------------------------------------------

def token_gaps(model, mix, w, rec, control=None, choices=None):
    """For every served token of one finished request: how far its logit
    lies below the reference's best at its position (0 where it is the
    reference's own first choice). With `control` (a key of `CONTROLS`) the
    gap of the token that the control puts first instead. `choices` gains
    the reference's (local, made) expert choices a layer over the request's
    own positions."""
    import jax.numpy as jnp

    p, n = len(rec["prompt"]), len(rec["tokens"])
    pad = next(x for x in sorted(mix["check_pad"]) if x >= p + n - 1)
    ids = np.zeros(pad, np.int32)
    ids[:p + n - 1] = np.concatenate([rec["prompt"], rec["tokens"][:-1]])
    rows = np.arange(p - 1, p - 1 + n)
    ref = ling_ref.served_logits(w, ids, rows, model, choices=choices,
                                 real=p + n - 1)
    if control is None:
        chosen = jnp.asarray(np.asarray(rec["tokens"], np.int32))
    else:
        chosen = jnp.argmax(ling_ref.served_logits(
            w, ids, rows, model, **CONTROLS[control]), axis=-1)
    picked = jnp.take_along_axis(ref, chosen[:, None], axis=-1)[:, 0]
    return np.asarray(jnp.max(ref, axis=-1) - picked)


def compare(model, mix, w, sample, control=None):
    """(`token_gap`: the worst over the sample's served tokens, their mean,
    tokens compared, the share in % of the reference's expert choices over
    the sample that fell on experts held here); `w` the seed's weights."""
    counted = []
    gaps = [token_gaps(model, mix, w, rec, control, counted)
            for rec in sample]
    if not gaps:
        return np.inf, np.inf, 0, None
    gaps = np.concatenate(gaps)
    share = 100.0 * sum(c[0] for c in counted) \
        / max(1, sum(c[1] for c in counted))
    print(f"[serve_kda] {control or 'served'} tokens against the reference: "
          f"{len(gaps)} compared, {int(np.sum(gaps > 0))} not its first "
          f"choice, worst {gaps.max():.5f}, mean {gaps.mean():.6f}; "
          f"{share:.3f} % of the reference's expert choices on experts "
          f"held here", flush=True)
    return float(gaps.max()), float(gaps.mean()), len(gaps), share


def check(cell, w, seed, finished, builds, control=None):
    """(the cell's `Checks` over the finished requests of a window, the
    share in % of the reference's expert choices over the checked requests
    that fell on experts held here); `w` the seed's weights, made again
    once the engine's are freed."""
    model, mix = cell["model"], cell["mix"]
    checks = harness.Checks(cell["limits"])
    sample = serve.pick_sample(finished, mix["check_requests"], seed)
    t0 = time.perf_counter()
    gap, mean, compared, share = compare(model, mix, w, sample, control)
    checks.add("token_gap", gap)
    checks.add("token_gap_mean", mean)
    checks.add("tokens_compared", compared, 1, at_most=False)
    checks.add("short_answers", sum(len(r["tokens"]) != r["max_new"]
                                    for r in finished), 0)
    checks.add("compiles_in_window", builds, 0)
    print(f"[serve_kda] reference over {len(sample)} of {len(finished)} "
          f"finished requests (a seeded sample of check_requests "
          f"{mix['check_requests']}, the longest among them), {compared} "
          f"served tokens in {time.perf_counter() - t0:.1f} s", flush=True)
    return checks, share


def run(cell, args, tools):
    import jax

    from paddle_tpu.models.gpt import GPTConfig

    model, mix = cell["model"], cell["mix"]
    counter, phase = tools["counter"], tools["phase"]
    # a program that lacks a key of this configuration stops here, before
    # 9 GB of weights are drawn for it
    GPTConfig(**model)
    reqs = generate.requests(mix, model["vocab_size"], args.seed)
    w = weights_ling.make(model, args.seed, mix["weights_dtype"])
    phase("requests and weights made")
    eng, pool = lin.build_server(cell, w)
    del w
    phase("model, engine and pool built")
    t0 = time.perf_counter()
    eng.warmup()
    phase("engine.warmup() done")
    print(f"[serve_kda] warmup() {time.perf_counter() - t0:.1f} s, "
          f"{eng.stats()['compiles']}", flush=True)
    serve.warm_traffic(cell, eng, pool, args.seed, tools["spans"])
    print(f"[serve_kda] warm traffic done; executables built so far "
          f"{counter.builds} ({counter.hits} from the persistent cache)",
          flush=True)

    sampler = StatsSampler(eng, mix["stats_every_s"])
    opened = tools["window_opened"]

    def window_opened(t):
        opened(t)
        sampler.start()

    win = serve.window(cell, eng, pool, reqs, args.seconds,
                       {**tools, "window_opened": window_opened}, args.seed)
    records, t_close, builds = win["records"], win["t_close"], win["builds"]
    samples = sampler.stop(t_close)
    device, reserved = harness.device_info(jax.devices(), cell["chips"])
    print(f"[serve_kda] memory_stats {jax.devices()[0].memory_stats()}",
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
    print(f"[serve_kda] window {args.seconds} s: {len(records)} requests "
          f"sent, {len(finished)} finished, {len(failed)} failed, "
          f"{delivered} tokens, {len(itl)} gaps, {builds} executable "
          f"build(s) inside it; first failure: "
          f"{failed[0].get('error') if failed else None}", flush=True)
    q = np.percentile(itl, [50, 90, 95, 99]).round(2).tolist() if itl else []
    print(f"[serve_kda] time to first token over {len(ttft)} requests: mean "
          f"{np.mean(ttft):.1f} ms, p90 {np.percentile(ttft, 90):.1f} ms; "
          f"gaps p50/p90/p95/p99 {q} ms (logged: no metric of this cell), "
          f"longest {np.sort(itl)[-3:][::-1].round(1).tolist()} ms; in the "
          f"window: " + ", ".join(f"{k} {b[k] - a[k]}" for k in (
              "steps", "prefill_chunks", "lin_chunk_tokens",
              "lin_step_tokens", "donated_dispatches", "pool_rebuilds",
              "moe_choices_total", "moe_choices_local",
              "moe_distinct_experts", "moe_expert_reads", "wedged_steps",
              "isolation_rounds", "timed_out"))
          + f"; state slots at most {b['lin_state_slots_peak']}, "
            f"{len(samples)} stats samples", flush=True)
    made = b["moe_choices_total"] - a["moe_choices_total"]
    local = b["moe_choices_local"] - a["moe_choices_local"]

    # ---- shut the program down and free it, then the reference
    lin.free_server(eng, pool)
    del eng, pool
    w = weights_ling.make(model, args.seed, mix["weights_dtype"])
    checks, ref_share = check(cell, w, args.seed, finished, builds)
    if finished:
        # the controls in the program's place on the longest request:
        # printed, not compared (the limit lies between `served` above and
        # the least of these; calibrate_kda.py reads them over the whole
        # sample)
        longest = serve.pick_sample(finished, 1, args.seed)
        for control in ("float8", "mean_decay", "no_bias"):
            compare(model, mix, w, longest, control)
    print(f"[serve_kda] expert choices on experts held here: the engine "
          f"{100.0 * local / max(1, made):.3f} % of {made} in the window, "
          f"the reference {ref_share} % over the checked requests",
          flush=True)
    del w
    return {
        "attempted": len(records), "failed": len(failed), "checks": checks,
        "device": device,
        "end_to_end": {
            "serve_tokens_per_s": delivered / args.seconds,
            "serve_itl_p95_ms": float(np.percentile(itl, 95))
            if itl else timeout_ms},
        "counters": {"snaps": win["snaps"], "records": records,
                     "stats_samples": samples,
                     "reserved_peak_bytes": reserved,
                     "reference_local_choice_share": ref_share,
                     "t_open": win["t_open"], "t_close": t_close,
                     "window_s": args.seconds, "requests": len(records),
                     "finished": len(finished), "gaps": len(itl)},
    }


trace_scope = lin.trace_scope
