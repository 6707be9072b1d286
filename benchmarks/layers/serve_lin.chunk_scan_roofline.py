"""The prompt chunks' program against the roofline: the least time of every
chunk of the prompts whose first token arrived in the traced stretch (the
larger of its operations over the bf16 peak and its bytes over the HBM
bandwidth: weights once, the state in and out, the rows attended and
written), over the device time of the prefill executables in the trace
(`trace_names.prefill`). The chunked form of the delta rule is plain
`jax.numpy` inside that program, no kernel of its own, so the program is
what there is to hold against the roofline."""
from benchmarks import flops_olmo_hybrid as fl


def read(ctx):
    s = ctx["scope"]
    names = ctx["mix"].get("trace_names", {}).get("prefill")
    if not s or not ctx["peaks"] or not names \
            or "layer_pattern" not in ctx["model"]:
        return None
    device_s = sum(s["module_s"].get(n, 0.0) for n in names)
    if not device_s or not s["prompts_finished"]:
        return None
    chunk = ctx["mix"]["engine"]["prefill_chunk"]
    least = 0.0
    for p in s["prompts_finished"]:
        for start, tokens in fl.prompt_chunks(p, chunk):
            least += max(
                fl.chunk_flops(ctx["model"], start, tokens)
                / ctx["peaks"]["bf16_flops"],
                fl.chunk_bytes(ctx["model"], start, tokens)
                / ctx["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / (device_s * ctx["cell"]["chips"])
