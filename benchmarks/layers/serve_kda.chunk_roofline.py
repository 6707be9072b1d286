"""The prompt chunks' program against the roofline: the least time of every
chunk of the prompts whose first token arrived in the traced stretch (the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth: weights outside the experts once, the experts chosen in it, the
state in and out, the rows met and written), over the device time of the
prefill executables in the trace (`trace_names.prefill`). The chunked form
of the per-channel delta rule is plain `jax.numpy` inside that program, no
kernel of its own, so the program is what there is to hold against the
roofline. A chunk's distinct experts and its choices on experts held are the
traced stretch's means a chunk and a position (the engine counts the chunks'
distinct experts apart from the steps', not chunk by chunk)."""
from benchmarks import flops_ling as fl


def read(ctx):
    s = ctx["scope"]
    snaps = ctx["counters"]["snaps"]
    names = ctx["mix"].get("trace_names", {}).get("prefill")
    if not s or not ctx["peaks"] or not names \
            or "moe_chunk_distinct_experts" not in snaps.get("trace1", {}):
        return None
    device_s = sum(s["module_s"].get(n, 0.0) for n in names)
    a, b = snaps["trace0"], snaps["trace1"]
    chunks = b["prefill_chunks"] - a["prefill_chunks"]
    if not device_s or not s["prompts_finished"] or not chunks:
        return None
    distinct = (b["moe_chunk_distinct_experts"]
                - a["moe_chunk_distinct_experts"]) / chunks
    local_share = (b["moe_choices_local"] - a["moe_choices_local"]) \
        / max(1, b["moe_choices_total"] - a["moe_choices_total"])
    model = ctx["model"]
    per_token = local_share * model["num_experts_per_tok"] \
        * fl.counts(model)[2]
    chunk = ctx["mix"]["engine"]["prefill_chunk"]
    least = 0.0
    for p in s["prompts_finished"]:
        for start, tokens in fl.prompt_chunks(p, chunk):
            least += max(
                fl.chunk_flops(model, start, tokens, per_token * tokens)
                / ctx["peaks"]["bf16_flops"],
                fl.chunk_bytes(model, start, tokens, distinct)
                / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (device_s * ctx["cell"]["chips"])
