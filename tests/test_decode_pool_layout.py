"""The paged KV pool's layout on the device (ISSUE 27).

A pool tensor is `[N, block_size, Hkv*D]`: flat rows whose minor dimension
is a multiple of the chip's 128 lanes, so the runtime keeps the array
row-major as it is. Stored as `[N, bs, Hkv, 64]` the runtime put N on the
lanes, and every step executable converted the whole pool to a gatherable
layout on its way in and back on its way out (PERF.md section 5).

Two proofs: the compiled TPU programs hold no relayout of the pool
(compile-only, against a described `v5e:2x2`, nothing runs), and on the CPU
the engine's tokens and the rows it wrote are the parent's bit for bit.
"""
import contextlib
import hashlib
import os
import re

import numpy as np
import pytest

import paddle_tpu as paddle
from paddle_tpu.inference import DecodeEngine
from paddle_tpu.models import gpt

# ---------------------------------------------------------------------------
# compile-only: what the chip's compiler makes of the pool
# ---------------------------------------------------------------------------

WIDE = dict(vocab_size=512, hidden_size=256, num_heads=4, num_layers=2,
            max_position_embeddings=1024)          # 4 heads x 64: Hkv*D = 256
DECODE_BUCKETS, PREFILL_BUCKET = (1, 4), 64


@pytest.fixture(scope="module")
def topo():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    try:
        return topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no libtpu, or its lock is held
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")


@pytest.fixture(scope="module")
def one_chip(topo):
    from jax.sharding import SingleDeviceSharding
    return SingleDeviceSharding(topo.devices[0])


@pytest.fixture
def no_jax_cache():
    """A compile for a described chip is written to jax's persistent cache
    but cannot be read back without the chip: keep it out of there."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache as cc

    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


def _computations(hlo):
    """{name: [instruction lines]} of an optimized HLO module's text, and
    the entry computation's name."""
    comps, entry, cur = {}, None, None
    for line in hlo.splitlines():
        m = re.match(r"(ENTRY )?%([\w.\-]+) \(.*\) -> .* \{$", line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            if m.group(1):
                entry = cur
        elif line.startswith("}"):
            cur = None
        elif cur is not None:
            comps[cur].append(line)
    return comps, entry


def _layout_of(type_text):
    """The layout of `dtype[dims]{layout}` less its memory space."""
    return re.sub(r"S\(\d+\)", "", type_text[type_text.index("{"):])


HLO_DTYPE = {"bfloat16": "bf16", "int8": "s8", "float32": "f32"}


def _pool_reading(compiled, tensors):
    """How the compiled program treats the pool tensors `tensors` (jax
    arrays, told apart by dtype and shape): the layouts they appear in,
    the `copy` / `transpose` / `copy-start` instructions that make one in
    the entry computation and in any other (the `while` body and what it
    calls), and the program's temporaries."""
    hlo = compiled.as_text()
    comps, entry = _computations(hlo)
    reading = {}
    for key in {(HLO_DTYPE[t.dtype.name], tuple(t.shape)) for t in tensors}:
        ty = re.escape(f"{key[0]}[{','.join(map(str, key[1]))}]")
        typed = re.compile(ty + r"\{[^}]*\}")
        made = re.compile(r"= (?:" + typed.pattern + r" (?:copy|transpose)\("
                          r"|\(" + typed.pattern + r", .* copy-start\()")
        copies = {name: sum(1 for line in lines if made.search(line))
                  for name, lines in comps.items()}
        reading[key] = {
            "layouts": sorted({_layout_of(m.group(0))
                               for m in typed.finditer(hlo)}),
            "entry_copies": copies.pop(entry, 0),
            "inner_copies": sum(copies.values())}
    return reading, compiled.memory_analysis().temp_size_in_bytes


def _aliased(compiled):
    """How many of the compiled program's outputs are one of its inputs'
    buffers: the entries of the module's `input_output_alias`."""
    head = compiled.as_text().split("\n", 1)[0]
    m = re.search(r"input_output_alias=\{(.*?)\}, \w+=", head)
    return 0 if m is None else len(re.findall(r"-alias\)", m.group(1)))


def _donated(compiled):
    """How many array leaves the program was told it may consume."""
    import jax

    return sum(bool(a.donated)
               for a in jax.tree_util.tree_leaves(compiled.args_info))


@contextlib.contextmanager
def _engine_compiled_for_chip(quant, one_chip, monkeypatch):
    """An engine over a `WIDE` bf16 model whose step programs compile for
    the described chip: yields (engine, {tag: compiled})."""
    import jax
    import jax.numpy as jnp
    from paddle_tpu.jit import aot

    built = {}

    def compile_for_chip(fn, avals, *, tag, donate_argnums=None, **_):
        avals = jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=one_chip), avals)
        kw = {} if donate_argnums is None else {
            "donate_argnums": donate_argnums}
        built[tag] = jax.jit(fn, **kw).lower(*avals).compile()
        return built[tag], "compiled"

    monkeypatch.setattr(aot, "compile_jit", compile_for_chip)
    paddle.seed(0)
    net = gpt("gpt_tiny", **WIDE)
    net.eval()
    for _, p in net.named_parameters():     # the served dtype: bf16 tiles
        p._value = p._value.astype(jnp.bfloat16)
    eng = DecodeEngine(net, max_length=1024, block_size=16, quant=quant,
                       decode_buckets=DECODE_BUCKETS,
                       prefill_buckets=(PREFILL_BUCKET,), prefill_chunk=0,
                       default_timeout=60.0)
    try:
        yield eng, built
    finally:
        eng.shutdown(drain_timeout=10.0)


@pytest.mark.parametrize("quant", [None, "int8"], ids=["bf16", "int8"])
def test_step_programs_hold_no_relayout_of_the_pool(
        quant, one_chip, no_jax_cache, monkeypatch, record_property):
    with _engine_compiled_for_chip(quant, one_chip, monkeypatch) as (
            eng, built):
        cfg = eng.model.cfg
        rows = cfg.num_kv_heads * cfg.head_dim
        assert cfg.head_dim == 64 and rows >= 128
        layer = eng.pool.tensors[0]
        pool_bytes = sum(t.size * t.dtype.itemsize
                         for ts in eng.pool.tensors for t in ts)
        values = [t for t in layer if t.shape[2:] == (rows,)]
        scales = [t for t in layer if t.shape[2:] != (rows,)]
        assert len(values) == 2 and len(scales) == 2 * (quant == "int8")
        for b in DECODE_BUCKETS:
            eng._decode_fn(b)
        eng._prefill_fn(PREFILL_BUCKET)
        tags = [f"decode-step-b{b}" for b in DECODE_BUCKETS] \
            + [f"decode-prefill-p{PREFILL_BUCKET}"]
        for tag in tags:
            got, temp = _pool_reading(built[tag], values)
            (got,) = got.values()
            record_property(f"{tag}.temp_bytes", temp)
            record_property(f"{tag}.values", got)
            if scales:              # recorded, not judged (PERF.md sec. 7)
                (read,) = _pool_reading(built[tag], scales)[0].values()
                record_property(f"{tag}.scales", read)
            # one layout wherever a pool tensor appears (argument, result,
            # loop carry, every fusion between them), so no copy has a
            # pool tensor in one layout as operand and in another as
            # result, and the loop copies none. Since the pool is donated
            # (ISSUE 35) every leaf's output IS its input, updated in
            # place: the program holds ONE pool, and what it returns
            # beside it is the tokens. (`entry_copies` stays recorded, not
            # judged: at this size the compiler stages a 2 MB tensor's
            # scatter in fast memory and copies it back, which a 68 MB
            # tensor of the served size does not fit.)
            assert len(got["layouts"]) == 1, (tag, got)
            assert got["inner_copies"] == 0, (tag, got)
            assert _aliased(built[tag]) == len(layer) * len(eng.pool.tensors)
            mem = built[tag].memory_analysis()
            assert mem.alias_size_in_bytes >= pool_bytes, (tag, mem)
            assert mem.output_size_in_bytes - mem.alias_size_in_bytes \
                < pool_bytes // 100, (tag, mem)
            assert temp < pool_bytes, (tag, temp, pool_bytes)


def _reached_from_loops(comps):
    """The computations a `while` runs: its body and condition, and
    whatever those call (fusions, nested loops, branches)."""
    called = re.compile(r"(?:body|condition|calls|to_apply)=%([\w.\-]+)"
                        r"|branch_computations=\{([^}]*)\}")

    def callees(line):
        for one, many in called.findall(line):
            yield from ([one] if one else re.findall(r"%([\w.\-]+)", many))

    todo = [c for lines in comps.values() for line in lines
            if " while(" in line
            for c in re.findall(r"(?:body|condition)=%([\w.\-]+)", line)]
    reached = set()
    while todo:
        name = todo.pop()
        if name not in reached:
            reached.add(name)
            todo += [c for line in comps[name] for c in callees(line)]
    return reached


def test_decode_step_reads_every_weight_once_outside_any_loop(
        one_chip, no_jax_cache, monkeypatch, record_property):
    """The plain decode step is one batched forward (ISSUE 29): a weight
    is an operand of instructions of the entry computation and of nothing
    a `while` runs. Under the scan of batch-1 forwards the weights were
    carried into the loop and read once a sequence. On the chip the dense
    layers are fusions, not `dot`s: shapes are matched, not opcodes."""
    bucket = DECODE_BUCKETS[-1]
    with _engine_compiled_for_chip(None, one_chip, monkeypatch) as (
            eng, built):
        eng._decode_fn(bucket)
        compiled = built[f"decode-step-b{bucket}"]
        weights = {n: tuple(p.shape) for n, p in eng._params.items()
                   if n.endswith(("qkv_proj.weight", "out_proj.weight",
                                  "up_proj.weight", "down_proj.weight",
                                  "wte.weight"))}
    assert len({n.rsplit(".", 2)[-2] for n in weights}) == 5, weights
    comps, entry = _computations(compiled.as_text())
    loops = _reached_from_loops(comps)
    assert entry not in loops
    record_property(f"decode-step-b{bucket}.temp_bytes",
                    compiled.memory_analysis().temp_size_in_bytes)
    record_property(f"decode-step-b{bucket}.loop_computations", len(loops))
    for shape in sorted(set(weights.values())):
        ty = f"bf16[{','.join(map(str, shape))}]{{"
        in_loops = [(name, line.strip()[:160]) for name in loops
                    for line in comps[name] if ty in line]
        assert not in_loops, (shape, in_loops[:3])
        params = [re.match(r"\s*%([\w.\-]+) = ", line).group(1)
                  for line in comps[entry]
                  if ty in line and " parameter(" in line]
        assert params, shape
        for name in params:
            used = re.compile(r"[(, ]%" + re.escape(name) + r"[,)]")
            readers = [line for line in comps[entry]
                       if used.search(line.split(" = ", 1)[-1])]
            assert readers, (shape, name)


# ---------------------------------------------------------------------------
# CPU: every program that writes the cache takes it donated (ISSUE 35)
# ---------------------------------------------------------------------------

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _rehearsal_model(name, **more):
    """A benchmark configuration at its rehearsal sizes."""
    import json
    from paddle_tpu.models.gpt import GPTConfig, GPTForCausalLM

    with open(os.path.join(ROOT, "benchmarks", "configs", name)) as f:
        conf = json.load(f)
    net = GPTForCausalLM(GPTConfig(**{**conf["model"], **conf["rehearsal"],
                                      **more}))
    net.eval()
    return net


def _kinds():
    """name -> (engine factory, tags of the programs that take the target's
    pool, tags of those that take the draft's)."""
    geo = dict(max_length=48, block_size=8, decode_buckets=(1, 2),
               prefill_buckets=(8, 16), prefill_chunk=16,
               default_timeout=60.0)
    steps = ["decode-step-b1", "decode-step-b2"]
    chunks = ["decode-prefill-p8", "decode-prefill-p16"]

    def tiny():
        paddle.seed(7)
        m = gpt("gpt_tiny", **TINY)
        m.eval()
        return m

    def dense(**kw):
        return DecodeEngine(tiny(), **geo, **kw)

    def spec():
        paddle.seed(3)
        draft = gpt("gpt_tiny", **{**TINY, "num_layers": 1})
        draft.eval()
        return DecodeEngine(tiny(), **geo, draft_model=draft, speculate_k=2)

    def mesh():
        from paddle_tpu.sharding import MeshConfig

        return DecodeEngine(tiny(), **geo, mesh=MeshConfig(tp=2, dp=4).build())

    def recurrent():
        return DecodeEngine(
            _rehearsal_model("olmo_hybrid_7b.json"),
            **{**geo, "max_length": 64, "block_size": 16,
               "prefill_buckets": (16, 32), "prefill_chunk": 32})

    def diffusion():
        return DecodeEngine(
            _rehearsal_model("sdar_30b_a3b.json"),
            block_diffusion={"block_length": 4, "denoising_steps": 2,
                             "mask_token_id": 255},
            **{**geo, "max_length": 64, "block_size": 16,
               "prefill_buckets": (16, 32), "prefill_chunk": 32})

    cow = ["decode-cow-copy"]
    return {
        "dense": (dense, steps + chunks + cow, []),
        "int8": (lambda: dense(quant="int8"), steps + chunks + cow, []),
        "tp_mesh": (mesh, steps + chunks + cow, []),
        "speculation": (spec, steps + chunks + cow
                        + ["decode-verify-b1", "decode-verify-b2"],
                        ["decode-propose-b1", "decode-propose-b2",
                         "decode-prefill-p8", "decode-prefill-p16"]),
        "recurrent": (recurrent, steps + ["decode-prefill-p16",
                                          "decode-prefill-p32"], []),
        "block_diffusion": (diffusion,
                            ["decode-step-bd-b1", "decode-step-bd-b2",
                             "decode-prefill-p16", "decode-prefill-p32"]
                            + cow, []),
    }


@pytest.mark.parametrize("kind", ["dense", "int8", "tp_mesh", "speculation",
                                  "recurrent", "block_diffusion"])
def test_every_program_that_writes_the_cache_aliases_every_leaf_of_it(
        kind, tmp_path, monkeypatch):
    """Compile-level, so it holds whatever a backend does with a donation
    at run time: each step, chunk, block-diffusion, verify, propose and
    draft catch-up executable is told it may consume every leaf of its
    pool (rows, int8 scales, recurrent state slots) and nothing else, and
    its module aliases each of those leaves to an output."""
    import jax
    from paddle_tpu.jit import aot

    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    built, real = [], aot.compile_jit

    def keeping(fn, avals, **kw):
        out = real(fn, avals, **kw)
        if out[0] is not None:          # not: a look into the cache alone
            built.append((kw["tag"], kw["donate_argnums"], avals, out[0]))
        return out

    monkeypatch.setattr(aot, "compile_jit", keeping)
    make, target_tags, draft_tags = _kinds()[kind]
    eng = make()
    try:
        eng.warmup()
        n_target = len(jax.tree_util.tree_leaves(eng.pool.tensors))
        n_draft = len(jax.tree_util.tree_leaves(eng.draft_pool.tensors)) \
            if draft_tags else 0
        if kind == "recurrent":
            n_state = len(jax.tree_util.tree_leaves(eng._state_tensors()))
            assert 0 < n_state < n_target
            (zero,) = [b for b in built if b[0] == "decode-zero-slot"]
            assert _donated(zero[3]) == _aliased(zero[3]) == n_state
        seen = sorted(tag for tag, *_ in built
                      if tag != "decode-zero-slot")
        assert seen == sorted(target_tags + draft_tags)
        for tag, donate, avals, compiled in built:
            if tag == "decode-zero-slot":
                continue
            (arg,) = donate
            n_pool = len(jax.tree_util.tree_leaves(avals[arg]))
            # the draft's catch-up shares its tag with the target's chunk:
            # told apart by the pool they take
            assert n_pool in {n_target, n_draft} - {0}, (tag, n_pool)
            assert _donated(compiled) == n_pool, (tag, _donated(compiled))
            assert _aliased(compiled) == n_pool, (tag, _aliased(compiled))
    finally:
        eng.shutdown(drain_timeout=10.0)


# ---------------------------------------------------------------------------
# CPU: tokens and written rows against the parent's, bit for bit
# ---------------------------------------------------------------------------

TINY = dict(vocab_size=97, hidden_size=48, num_heads=4, num_kv_heads=2,
            num_layers=2, rope=True, swiglu=True, rms_norm=True,
            max_position_embeddings=64, tie_word_embeddings=False)

# Recorded from the parent commit (3d78f20, pool [N, bs, Hkv, D]) by this
# very procedure: the served tokens, and sha256[:16] of every pool tensor's
# bytes in the engine's (layer, entry) order. The tokens, layer 0's rows
# and layer 1's int8 values are 3d78f20's still. Layer 1's float rows and
# scales were recorded again at ISSUE 31, by the same procedure: the
# model's grouped-query heads attend as rows of their KV head since then
# (`_cached_attn_core`, no K/V repeat), which sums the same float32
# products in another order, so what layer 1 projects from layer 0's
# attention differs in its last bits (3d78f20 read a37cb7b08792039b,
# 4404ddd64b55e39e and, int8, 560fedbc4dd22f00, 0ff619f79702e48f).
PARENT = {
    None: ([[91, 53, 78, 72, 87, 49, 14, 72, 87, 53],
            [1, 14, 72, 87, 19, 24]],
           ["909b117860808f1f", "93f22409611614d1",
            "9614ddc65af2addb", "d2b92a8527751b07"]),
    "int8": ([[91, 53, 78, 72, 87, 49, 14, 72, 87, 53],
              [1, 14, 72, 87, 19, 24]],
             ["70da0cc5427ea6e8", "f2f1993b2feca6a6",
              "7dff4527cf5dd7ba", "9c9ccf1968f84290",
              "f3f13ac891b810ca", "da9f9995a0b72eec",
              "83529f4947f08db6", "d61562c52d1380e1"]),
}


def _prompt(seed, n=6):
    return np.random.RandomState(seed).randint(
        0, TINY["vocab_size"], (n,)).astype(np.int32)


@pytest.mark.parametrize("quant", [None, "int8"], ids=["bf16", "int8"])
def test_tokens_and_written_rows_equal_the_parents(quant, tmp_path,
                                                   monkeypatch):
    """One sequence after the other, so which block holds what is the
    allocator's own order: a one-chunk prompt, then one of two chunks. A
    flat row is the parent's [Hkv, D] row with its bytes in the same
    order, so the digests compare the pools as [N, bs, Hkv, D]."""
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    paddle.seed(7)
    m = gpt("gpt_tiny", **TINY)
    m.eval()
    eng = DecodeEngine(m, max_length=48, block_size=8,
                       decode_buckets=(1, 2, 4), prefill_buckets=(8, 16),
                       prefill_chunk=8, quant=quant, default_timeout=60.0)
    try:
        tokens = [list(map(int, eng.generate(_prompt(3), 10))),
                  list(map(int, eng.generate(_prompt(1, 13), 6)))]
        hkv, d = m.cfg.num_kv_heads, m.cfg.head_dim
        digests = []
        for layer in eng.pool.tensors:
            for t in layer:
                a = np.asarray(t)
                if a.shape[2:] == (hkv * d,):
                    a = a.reshape(a.shape[:2] + (hkv, d))
                else:
                    assert a.shape[2:] == (hkv,)      # int8 scales
                digests.append(hashlib.sha256(
                    np.ascontiguousarray(a).tobytes()).hexdigest()[:16])
    finally:
        eng.shutdown(drain_timeout=10.0)
    want_tokens, want_digests = PARENT[quant]
    assert tokens == want_tokens
    assert digests == want_digests
