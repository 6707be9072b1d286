"""paddle_tpu.analysis.graphcheck — the graph auditor.

The analysis family covers what we *wrote* (tracelint, pure AST) and what
we *ran* (tpu-san, runtime probes) — this module audits what XLA actually
**compiled**. It statically walks the ClosedJaxpr and (when available)
the lowered/compiled HLO of every framework entrypoint — engine
train/eval steps, AOT bucket executables (`jit/aot.compile_jit` /
`compile_batched`), exported `TranslatedLayer` calls, `DecodeEngine`
prefill/decode steps — and emits site-keyed findings for graph-level
properties no source lint or runtime probe can see:

* **GC001 unexpected-collective** — collective ops (all-gather,
  all-reduce, reduce-scatter, all-to-all, collective-permute) in a graph
  whose *declared* placement (the `AxisRules`-resolved specs the
  entrypoint was compiled with) uses no sharded mesh axis, or an
  all-gather materializing the FULL value of a parameter the placement
  declared sharded (the rule table failed: "all-gather-everything").
* **GC002 full-replication** — a large operand (default ≥ 16 MiB,
  ``PADDLE_TPU_GRAPHCHECK_REPL_MB``) declared fully replicated on a mesh
  that offers a model-sharding axis (fsdp/tp/mp/sharding/expert) with
  size > 1 — silent replication where sharding was configured.
* **GC003 conv-layout-change** — a layout ``transpose``/``copy`` inside
  a conv/pool region of the jaxpr (within a few def-use hops of a
  `conv_general_dilated`/`reduce_window`): the NHWC enforcement guard —
  no layout changes smuggled into the conv stack.
* **GC004 host-transfer** — a device-to-host transfer compiled INTO the
  graph: callback primitives (`pure_callback`/`io_callback`/
  `debug_callback`/`debug_print`) in the jaxpr, or infeed/outfeed in
  the HLO.
* **GC005 donation-unaliased** — an argument declared donated whose
  buffers do NOT appear in the executable's input-output aliasing table:
  the donation silently bought nothing (the static complement of
  tpu-san's runtime use-after-donate guard; catchable on the CPU mesh
  where the runtime bug would only crash on TPU).
* **GC006 memory-watermark** — an estimated live-memory high-water mark
  per entrypoint (liveness scan over the jaxpr), ratcheted per site
  through the baseline (regression slack
  ``PADDLE_TPU_GRAPHCHECK_MEM_SLACK``, default 0.25) and optionally
  budgeted (``PADDLE_TPU_GRAPHCHECK_MEM_MB``).
* **GC000 audit-error** — the auditor itself failed on an entrypoint
  (never baselined silently; mirrors tracelint's TL000).

Opt-in via ``PADDLE_TPU_GRAPHCHECK=1`` (or :func:`enable`) with the
established zero-overhead-off discipline: every framework hook reduces
to one module-flag check when off. When on, the compile paths call
:func:`audit_executable` — reusing the lowered/compiled objects they
already built where possible (the engine pays one extra AOT
lower+compile per cold entrypoint, documented in
docs/static_analysis.md).

Findings are keyed **site-wise and line-number-free**
(``<site>::<rule>``, e.g. ``engine.step::GC005``) and ratchet through a
checked-in ``.graphcheck_baseline.json`` driven by
``tools/graph_audit.py`` (exit 0 clean / 1 new / 2 usage) — the same
determinism contract as tracelint and tpu-san. Counts export as the
``graphcheck`` collector on the obs registry.
"""
from __future__ import annotations

import os
import re
import threading

__all__ = [
    "RULES", "Finding", "enable", "disable", "enabled", "reset",
    "audit_executable", "findings", "counts_by_key", "watermarks",
    "report", "assert_clean", "load_baseline", "write_baseline",
    "new_counts", "new_watermarks", "jaxpr_watermark",
    "params_bytes_per_chip", "GraphCheckError",
    "OBS_COLLECTOR",
]

_ENV = "PADDLE_TPU_GRAPHCHECK"
_ENV_REPL_MB = "PADDLE_TPU_GRAPHCHECK_REPL_MB"
_ENV_GATHER_BYTES = "PADDLE_TPU_GRAPHCHECK_GATHER_MIN_BYTES"
_ENV_MEM_MB = "PADDLE_TPU_GRAPHCHECK_MEM_MB"
_ENV_MEM_SLACK = "PADDLE_TPU_GRAPHCHECK_MEM_SLACK"

RULES = {
    "GC000": "audit-error: the auditor failed on this entrypoint",
    "GC001": "unexpected collective vs the declared placement",
    "GC002": "large operand fully replicated on a model-sharding mesh",
    "GC003": "layout transpose/copy inside a conv/pool region",
    "GC004": "device-to-host transfer compiled into the graph",
    "GC005": "donation declared but absent from input-output aliasing",
    "GC006": "estimated live-memory watermark over budget/ratchet",
}

#: obs-registry collector name (docs/observability.md)
OBS_COLLECTOR = "graphcheck"

#: per-key cap on stored Finding exemplars (counts stay exact)
_MAX_SAMPLES = 5

#: mesh axes whose presence (size > 1) declares a model-sharding intent —
#: replicating a large operand there is *accidental* (GC002); a dp-only
#: mesh replicates parameters by design and is exempt
MODEL_AXES = ("fsdp", "tp", "mp", "sharding", "expert")

#: HLO collective kinds GC001 recognizes
_COLLECTIVES = ("all-gather", "all-reduce", "reduce-scatter",
                "all-to-all", "collective-permute", "collective-broadcast")

#: jaxpr primitives that anchor a conv/pool region (GC003)
_CONV_ANCHORS = {
    "conv_general_dilated", "reduce_window", "reduce_window_max",
    "reduce_window_min", "reduce_window_sum", "select_and_scatter_add",
}

#: elementwise/shape prims a layout change can hide behind without leaving
#: the conv region (GC003 proximity hops)
_PASSTHROUGH = {
    "add", "sub", "mul", "div", "max", "min", "neg", "abs", "exp", "log",
    "tanh", "logistic", "rsqrt", "sqrt", "sign", "integer_pow", "pow",
    "select_n", "convert_element_type", "broadcast_in_dim", "reshape",
    "squeeze", "expand_dims", "custom_jvp_call", "custom_vjp_call",
    "custom_vjp_call_jaxpr", "jit", "clamp", "ge", "gt", "le", "lt",
}

#: jaxpr primitives that ARE host transfers (GC004)
_HOST_PRIMS = {"pure_callback", "io_callback", "debug_callback",
               "debug_print", "infeed", "outfeed"}

#: GC003 def-use proximity (hops through _PASSTHROUGH prims)
_CONV_HOPS = 3

_off_values = ("", "0", "false", "off", "no")


def _env_on(name, default=""):
    return os.environ.get(name, default).strip().lower() not in _off_values


def _env_float(name, default):
    try:
        return float(os.environ.get(name, "") or default)
    except ValueError:
        return default


_enabled = _env_on(_ENV)


class GraphCheckError(RuntimeError):
    """Raised by assert_clean when the auditor holds findings."""


class Finding:
    """One auditor hit. `key` is the baseline identity — site and rule
    only, no line numbers, no instance ids — so the ratchet never churns
    when code moves."""

    __slots__ = ("rule", "site", "message")

    def __init__(self, rule, site, message):
        self.rule = rule
        self.site = site
        self.message = message

    @property
    def key(self):
        return f"{self.site}::{self.rule}"

    def to_dict(self):
        return {"rule": self.rule, "site": self.site,
                "message": self.message}

    def __repr__(self):
        return f"[{self.rule}] {self.site}: {self.message}"


class _Registry:
    """Global recorder. Guarded by a RAW threading.Lock on purpose (the
    analysis recorders must not observe themselves through lockcheck)."""

    def __init__(self):
        self._mu = threading.Lock()
        self._counts = {}       # finding key -> exact count
        self._samples = {}      # finding key -> [Finding] (capped)
        self._watermarks = {}   # site -> max estimated live bytes
        self.counters = {"audits": 0, "compiled_audits": 0,
                         "collectives_seen": 0}

    def record(self, rule, site, message):
        f = Finding(rule, site, message)
        with self._mu:
            self._counts[f.key] = self._counts.get(f.key, 0) + 1
            samples = self._samples.setdefault(f.key, [])
            if len(samples) < _MAX_SAMPLES:
                samples.append(f)
        return f

    def bump(self, name, n=1):
        """Counter increment under the registry lock: concurrent audits
        (decode step-pool thread vs serving workers) must not lose
        updates or race reset()'s dict replacement."""
        with self._mu:
            self.counters[name] = self.counters.get(name, 0) + n

    def note_watermark(self, site, nbytes):
        with self._mu:
            prev = self._watermarks.get(site, 0)
            if nbytes > prev:
                self._watermarks[site] = int(nbytes)

    def findings(self):
        with self._mu:
            return [f for ss in self._samples.values() for f in ss]

    def counts_by_key(self):
        with self._mu:
            return dict(self._counts)

    def watermarks(self):
        with self._mu:
            return dict(self._watermarks)

    def reset(self):
        with self._mu:
            self._counts = {}
            self._samples = {}
            self._watermarks = {}
            self.counters = {k: 0 for k in self.counters}

    def report(self):
        with self._mu:
            return {
                "counts": dict(self._counts),
                "findings": [f.to_dict() for ss in self._samples.values()
                             for f in ss],
                "by_rule": {
                    r: sum(n for k, n in self._counts.items()
                           if k.endswith("::" + r)) for r in RULES},
                "watermarks": dict(self._watermarks),
                "counters": dict(self.counters),
            }


_registry = _Registry()


def registry():
    return _registry


def _obs_collect():
    rep = _registry.report()
    out = {"enabled": int(_enabled),
           "findings": sum(rep["counts"].values()),
           "sites_watermarked": len(rep["watermarks"])}
    out.update({r.lower(): n for r, n in rep["by_rule"].items()})
    out.update(rep["counters"])
    return out


def enable():
    """Turn the auditor on (hooks audit on their next cold compile) and
    register the ``graphcheck`` obs collector."""
    global _enabled
    _enabled = True
    try:
        from ..obs.metrics import registry as _obs
        _obs().register_collector(OBS_COLLECTOR, _obs_collect)
    except Exception:  # tpu-lint: disable=TL007 — obs is optional here:
        pass           # the auditor must work without the registry


def disable():
    global _enabled
    _enabled = False
    try:
        from ..obs.metrics import registry as _obs
        _obs().unregister_collector(OBS_COLLECTOR)
    except Exception:  # tpu-lint: disable=TL007 — symmetric with enable
        pass


def enabled():
    return _enabled


def reset():
    """Clear all recorded state (the enable flag stays)."""
    _registry.reset()


if _enabled:
    enable()     # env asked: register the collector at import


# ---------------------------------------------------------------------------
# jaxpr walking
# ---------------------------------------------------------------------------

def _inner_jaxprs(eqn):
    """Sub-jaxprs of one eqn (pjit/scan/cond/custom_* bodies)."""
    out = []
    for v in eqn.params.values():
        vs = v if isinstance(v, (list, tuple)) else (v,)
        for q in vs:
            inner = getattr(q, "jaxpr", None)
            if inner is None:
                continue
            # ClosedJaxpr (scan/pjit params) or raw Jaxpr (custom_jvp)
            out.append(_unwrap(inner))
    return out


def _unwrap(jaxpr):
    """Raw Jaxpr behind a ClosedJaxpr (which forwards .eqns but not the
    var lists the liveness scan needs)."""
    inner = getattr(jaxpr, "jaxpr", None)
    return inner if inner is not None and hasattr(inner, "eqns") else jaxpr


def _walk_jaxprs(jaxpr):
    """Yield every (sub)jaxpr, outermost first."""
    stack = [_unwrap(jaxpr)]
    while stack:
        j = stack.pop()
        yield j
        for e in j.eqns:
            stack.extend(_inner_jaxprs(e))


def _aval_bytes(aval):
    try:
        return int(aval.size) * int(aval.dtype.itemsize)
    except (AttributeError, TypeError):
        return 0


def _prim_name(eqn):
    return eqn.primitive.name


# -- GC003: layout transposes inside conv/pool regions ----------------------

#: call-like prims GC003 inlines so def-use chains survive the op
#: registry's per-op jit boundaries (every framework op traces as its
#: own pjit eqn — without inlining, a transpose and the conv it feeds
#: never share a jaxpr)
_CALL_PRIMS = {"jit", "custom_jvp_call", "custom_vjp_call",
               "custom_vjp_call_jaxpr", "remat", "checkpoint",
               "closed_call", "core_call"}

_MAX_INLINE_DEPTH = 12


def _is_literal(v):
    return type(v).__name__ == "Literal"


def _inline_units(jaxpr):
    """Flatten into def-use 'units': lists of
    ``(prim_name, in_reps, out_reps, eqn)`` with call-like prims inlined
    (inner vars aliased onto the call boundary vars). scan/cond/while
    bodies become separate units — no cross-iteration chains."""
    roots = [_unwrap(jaxpr)]
    units = []
    while roots:
        root = roots.pop()
        alias = {}
        flat = []

        def rep(v, _alias=alias):
            while v in _alias:
                v = _alias[v]
            return v

        def walk(j, depth, _alias=alias, _flat=flat):
            for e in j.eqns:
                name = _prim_name(e)
                inner = _inner_jaxprs(e)
                if name in _CALL_PRIMS and len(inner) == 1 and \
                        depth < _MAX_INLINE_DEPTH:
                    ij = inner[0]
                    for iv, ov in zip(ij.invars, e.invars):
                        if not _is_literal(ov):
                            _alias[iv] = ov
                    walk(ij, depth + 1)
                    for outer_ov, inner_ov in zip(e.outvars, ij.outvars):
                        if not _is_literal(inner_ov):
                            _alias[outer_ov] = inner_ov
                    continue
                if inner:
                    roots.extend(inner)
                ins = [rep(v) for v in e.invars if not _is_literal(v)]
                outs = [rep(v) for v in e.outvars]
                _flat.append((name, ins, outs, e))

        walk(root, 0)
        units.append(flat)
    return units


def _conv_layout_findings(jaxpr):
    """(message,) per transpose/copy eqn within _CONV_HOPS def-use hops
    of a conv/pool anchor, over the call-inlined units."""
    out = []
    for unit in _inline_units(jaxpr):
        anchor_set = {i for i, (name, *_r) in enumerate(unit)
                      if name in _CONV_ANCHORS}
        if not anchor_set:
            continue
        producer = {}    # rep var -> eqn index
        consumers = {}   # rep var -> [eqn index]
        for i, (_n, ins, outs, _e) in enumerate(unit):
            for v in outs:
                producer[v] = i
            for v in ins:
                consumers.setdefault(v, []).append(i)

        def _reaches_anchor(start_idx, forward, _unit=unit,
                            _anchor=anchor_set, _prod=producer,
                            _cons=consumers):
            seen = {start_idx}
            frontier = [start_idx]
            for _ in range(_CONV_HOPS):
                nxt = []
                for i in frontier:
                    _n, ins, outs, _e = _unit[i]
                    steps = [c for v in outs for c in _cons.get(v, ())] \
                        if forward else \
                        [_prod[v] for v in ins if v in _prod]
                    for s in steps:
                        if s in seen:
                            continue
                        if s in _anchor:
                            return True
                        seen.add(s)
                        if _unit[s][0] in _PASSTHROUGH:
                            nxt.append(s)
                frontier = nxt
            return False

        for i, (name, _ins, _outs, e) in enumerate(unit):
            if name not in ("transpose", "copy"):
                continue
            if _reaches_anchor(i, forward=True) or \
                    _reaches_anchor(i, forward=False):
                aval = e.outvars[0].aval if e.outvars else None
                perm = e.params.get("permutation")
                desc = f" permutation={tuple(perm)}" if perm is not None \
                    else ""
                shape = tuple(getattr(aval, "shape", ()))
                out.append(
                    f"layout `{name}`{desc} -> {shape} within "
                    f"{_CONV_HOPS} def-use hops of a conv/pool op — a "
                    f"layout change smuggled into the conv stack (keep "
                    f"the stack NHWC end-to-end)")
    return out


# -- GC004: host transfers --------------------------------------------------

def _host_transfer_findings(jaxpr, hlo_text):
    out = []
    for j in _walk_jaxprs(jaxpr):
        for e in j.eqns:
            name = _prim_name(e)
            if name in _HOST_PRIMS or name.endswith("_callback"):
                out.append(
                    f"`{name}` primitive compiled into the graph — every "
                    f"dispatch round-trips to the host")
    if hlo_text:
        for kind in ("outfeed", "infeed"):
            n = len(re.findall(rf"\b{kind}\(", hlo_text))
            if n:
                out.append(f"{n} `{kind}` op(s) in the compiled HLO")
    return out


# -- GC006: live-memory watermark -------------------------------------------

def jaxpr_watermark(jaxpr):
    """Estimated live-memory high-water mark (bytes) of a (Closed)Jaxpr:
    a liveness scan over the eqn sequence — inputs/consts live from the
    start, each eqn's outputs become live at the eqn, operands die after
    their last use, outvars live to the end. Sub-jaxpr watermarks (scan/
    cond/pjit bodies) stack on top of the live set at their eqn. An
    estimate (XLA fusion/rematerialization moves the real number), but a
    deterministic one — which is what a ratchet needs."""
    j = _unwrap(jaxpr)
    is_var = lambda v: type(v).__name__ != "Literal"  # noqa: E731
    last_use = {}
    for i, e in enumerate(j.eqns):
        for v in e.invars:
            if is_var(v):
                last_use[v] = i
    live_forever = set(v for v in j.outvars if is_var(v))
    live = {}
    for v in list(j.invars) + list(j.constvars):
        live[v] = _aval_bytes(v.aval)
    peak = sum(live.values())
    for i, e in enumerate(j.eqns):
        for v in e.outvars:
            live[v] = _aval_bytes(v.aval)
        here = sum(live.values())
        inner = max((jaxpr_watermark(sj) for sj in _inner_jaxprs(e)),
                    default=0)
        peak = max(peak, here + inner)
        for v in list(e.invars) + list(e.outvars):
            if is_var(v) and last_use.get(v) == i and v not in live_forever:
                live.pop(v, None)
    return peak


def params_bytes_per_chip(param_avals, param_specs, mesh):
    """Estimated per-chip residency (bytes) of the entrypoint's declared
    parameter/state set: each aval's bytes scaled by its spec's shard
    fraction on `mesh`. The jaxpr watermark above is GLOBAL logical bytes
    — avals don't shrink when a tensor shards — so the fsdp memory story
    ("params + optimizer state hold ~1/N per chip") needs this sibling
    number. Deterministic given (avals, specs, mesh), which is what the
    per-site GC006 ratchet requires; recorded under ``<site>::params``."""
    from ..sharding import shard_fraction

    total = 0.0
    for n, aval in param_avals.items():
        spec = param_specs.get(n)
        frac = shard_fraction(spec, mesh) if spec is not None else 1.0
        total += _aval_bytes(aval) * frac
    return int(total)


# -- GC001 / GC002 helpers ---------------------------------------------------

def _spec_axes(spec):
    """Mesh-axis names a PartitionSpec(-like) references."""
    axes = set()
    for entry in tuple(spec or ()):
        if entry is None:
            continue
        for a in ((entry,) if isinstance(entry, str) else tuple(entry)):
            axes.add(a)
    return axes


def _shardings_leaves(in_shardings):
    """Flat NamedSharding-ish leaves of an in_shardings pytree."""
    if in_shardings is None:
        return []
    import jax

    leaves, _ = jax.tree_util.tree_flatten(
        in_shardings, is_leaf=lambda x: hasattr(x, "spec"))
    return [s for s in leaves if hasattr(s, "spec")]


_HLO_COLLECTIVE_RE = re.compile(
    r"=\s+(?:\()?([a-z0-9]+)\[([0-9,]*)\][^ ]*\s+"
    r"(all-gather|all-reduce|reduce-scatter|all-to-all|collective-permute|"
    r"collective-broadcast)\(")


def _hlo_collectives(hlo_text):
    """[(kind, dtype, dims)] for every collective op in compiled HLO."""
    out = []
    for m in _HLO_COLLECTIVE_RE.finditer(hlo_text or ""):
        dtype, dims, kind = m.group(1), m.group(2), m.group(3)
        shape = tuple(int(d) for d in dims.split(",") if d) if dims else ()
        out.append((kind, dtype, shape))
    return out


_HLO_DTYPES = {
    "float32": "f32", "float16": "f16", "bfloat16": "bf16",
    "float64": "f64", "int32": "s32", "int64": "s64", "int16": "s16",
    "int8": "s8", "uint32": "u32", "uint8": "u8", "bool": "pred",
}


def _hlo_dtype(dtype):
    return _HLO_DTYPES.get(str(dtype), str(dtype))


_ALIAS_PARAM_RE = re.compile(r"\(\s*(\d+)\s*,\s*\{")


def _aliased_params(hlo_text):
    """Parameter indices in the compiled module's input_output_alias
    table (``input_output_alias={ {0}: (2, {}, may-alias), ... }`` —
    nested braces, so a balanced scan rather than a lazy regex)."""
    marker = "input_output_alias={"
    start = (hlo_text or "").find(marker)
    if start < 0:
        return set()
    i = start + len(marker)
    depth = 1
    while i < len(hlo_text) and depth:
        c = hlo_text[i]
        if c == "{":
            depth += 1
        elif c == "}":
            depth -= 1
        i += 1
    body = hlo_text[start + len(marker): i - 1]
    return {int(g) for g in _ALIAS_PARAM_RE.findall(body)}


# ---------------------------------------------------------------------------
# the audit
# ---------------------------------------------------------------------------

def audit_executable(site, *, jit_obj=None, args=None, fn=None,
                     lowered=None, compiled=None, mesh=None,
                     axes_specs=None, in_shardings=None, param_avals=None,
                     param_specs=None, expect_sharded_params=False):
    """Audit one framework entrypoint; returns the findings recorded.

    Two call shapes:

    * ``audit_executable(site, jit_obj=jitted, args=(...))`` — the
      auditor traces, lowers and compiles itself (one extra AOT compile;
      the engine's cold path, opt-in only).
    * ``audit_executable(site, fn=f, args=avals, lowered=l, compiled=c)``
      — the aot compile paths hand over the objects they already built;
      only one extra (cheap) trace for the jaxpr.

    Context: `mesh` + `axes_specs`/`in_shardings` declare the intended
    placement (GC001/GC002); `param_avals`+`param_specs` name parameters
    for the full-gather check, armed by `expect_sharded_params=True`
    (serving/TP entrypoints, where parameters must STAY sharded — fsdp
    training gathers in-graph by design and passes False).

    Never raises: an auditor failure records a GC000 finding (the
    entrypoint still runs; the ratchet surfaces the breakage).
    """
    found = []
    _registry.bump("audits")
    try:
        import jax

        # ---- jaxpr ----------------------------------------------------
        if jit_obj is not None:
            traced = jit_obj.trace(*args)
            jaxpr = traced.jaxpr
            if lowered is None:
                lowered = traced.lower()
        else:
            jaxpr = jax.jit(fn).trace(*args).jaxpr
        hlo_text = ""
        if compiled is None and lowered is not None:
            compiled = lowered.compile()
        if compiled is not None:
            _registry.bump("compiled_audits")
            try:
                hlo_text = compiled.as_text()
            except Exception:  # tpu-lint: disable=TL007 — some backends
                hlo_text = ""  # cannot render text; jaxpr rules still run

        def rec(rule, msg):
            found.append(_registry.record(rule, site, msg))

        # ---- GC003 / GC004 / GC006 (jaxpr) ----------------------------
        for msg in _conv_layout_findings(jaxpr):
            rec("GC003", msg)
        for msg in _host_transfer_findings(jaxpr, hlo_text):
            rec("GC004", msg)
        watermark = jaxpr_watermark(jaxpr)
        _registry.note_watermark(site, watermark)
        if param_avals and param_specs is not None and mesh is not None:
            # per-chip param/state residency rides the same watermark
            # ratchet under its own site key (see params_bytes_per_chip)
            _registry.note_watermark(
                site + "::params",
                params_bytes_per_chip(param_avals, param_specs, mesh))
        budget_mb = _env_float(_ENV_MEM_MB, 0.0)
        if budget_mb and watermark > budget_mb * (1 << 20):
            rec("GC006",
                f"estimated live-memory watermark {watermark} bytes "
                f"exceeds the {budget_mb} MiB budget "
                f"({_ENV_MEM_MB})")

        # ---- declared placement context -------------------------------
        specs = list(axes_specs or ())
        for sh in _shardings_leaves(in_shardings):
            specs.append(sh.spec)
            if mesh is None:
                mesh = getattr(sh, "mesh", None)
        mesh_sizes = dict(mesh.shape) if mesh is not None else {}
        declared_axes = set()
        for s in specs:
            declared_axes |= {a for a in _spec_axes(s)
                              if mesh_sizes.get(a, 1) > 1}

        # ---- GC001: collectives vs declared placement -----------------
        colls = _hlo_collectives(hlo_text)
        _registry.bump("collectives_seen", len(colls))
        if colls and not declared_axes:
            by_kind = {}
            for kind, dtype, shape in colls:
                by_kind.setdefault(kind, []).append((dtype, shape))
            for kind, insts in sorted(by_kind.items()):
                rec("GC001",
                    f"{len(insts)} `{kind}` op(s) (e.g. "
                    f"{insts[0][0]}{list(insts[0][1])}) in a graph whose "
                    f"declared placement is fully replicated — no rule "
                    f"resolved a sharded axis, yet the compiled program "
                    f"communicates")
        if expect_sharded_params and param_avals and param_specs:
            gather_min = int(_env_float(_ENV_GATHER_BYTES, 4096))
            sharded_full = {}
            for n, aval in param_avals.items():
                s = param_specs.get(n)
                if s is None or not _spec_axes(s):
                    continue
                if _aval_bytes(aval) < gather_min:
                    continue
                key = (_hlo_dtype(aval.dtype), tuple(aval.shape))
                sharded_full.setdefault(key, n)
            for kind, dtype, shape in colls:
                if kind != "all-gather":
                    continue
                n = sharded_full.get((dtype, shape))
                if n is not None:
                    rec("GC001",
                        f"all-gather materializes the FULL value "
                        f"{dtype}{list(shape)} of parameter '{n}' that the "
                        f"placement declared sharded "
                        f"({tuple(param_specs[n])}) — the rule table "
                        f"failed; the parameter replicates at every call")

        # ---- GC002: accidental full replication -----------------------
        model_axes = [a for a in MODEL_AXES if mesh_sizes.get(a, 1) > 1]
        if model_axes:
            repl_min = int(_env_float(_ENV_REPL_MB, 16.0) * (1 << 20))
            operands = []
            if param_avals and param_specs is not None:
                operands = [(n, a, param_specs.get(n))
                            for n, a in param_avals.items()]
            elif in_shardings is not None and args:
                avals = [getattr(a, "aval", a) for a in
                         jax.tree_util.tree_leaves(list(args))]
                shs = _shardings_leaves(in_shardings)
                if len(avals) == len(shs):
                    operands = [(f"operand[{i}]", a, sh.spec)
                                for i, (a, sh) in enumerate(zip(avals, shs))]
            for n, aval, s in operands:
                nbytes = _aval_bytes(aval)
                if nbytes >= repl_min and (s is None or not _spec_axes(s)):
                    rec("GC002",
                        f"operand '{n}' ({nbytes >> 20} MiB) is fully "
                        f"replicated while the mesh offers model-sharding "
                        f"axes {model_axes} — every device holds a full "
                        f"copy")

        # ---- GC005: donation vs input-output aliasing -----------------
        if lowered is not None and compiled is not None:
            ainfo = getattr(lowered, "args_info", None)
            if ainfo is not None:
                aliased = _aliased_params(hlo_text)
                # jax PRUNES unused arguments from the compiled module,
                # shifting HLO parameter numbering — map flat leaf index
                # -> HLO parameter index through kept_var_idx. When the
                # mapping is unavailable, degrade to the unambiguous
                # empty-table case only (never a shifted-index false
                # positive).
                kept = None
                try:
                    kept = lowered._lowering.compile_args.get(
                        "kept_var_idx")
                except Exception:  # tpu-lint: disable=TL007 — private
                    kept = None    # jax surface; degrade, don't break
                param_of = {flat: rank
                            for rank, flat in enumerate(sorted(kept))} \
                    if kept is not None else None
                flat_idx = 0
                for argnum, sub in enumerate(
                        ainfo[0] if isinstance(ainfo, tuple) and
                        len(ainfo) == 2 and isinstance(ainfo[1], dict)
                        else ainfo):
                    leaves = jax.tree_util.tree_leaves(sub)
                    idxs = range(flat_idx, flat_idx + len(leaves))
                    flat_idx += len(leaves)
                    donated = [l for l in leaves
                               if getattr(l, "donated", False)]
                    if not donated:
                        continue
                    if param_of is not None:
                        params = [param_of[i] for i in idxs
                                  if i in param_of]
                        if not params:
                            continue    # arg entirely pruned: unused,
                            #             not an aliasing failure
                        bad = not any(p in aliased for p in params)
                    else:
                        bad = not aliased
                    if bad:
                        rec("GC005",
                            f"argument {argnum} ({len(leaves)} leaves) is "
                            f"declared donated but NONE of its buffers "
                            f"appear in the executable's input-output "
                            f"aliasing — the donation bought nothing "
                            f"(dtype/shape/sharding mismatch between the "
                            f"donated input and every output?)")
    except Exception as e:  # noqa: BLE001 — the auditor must never break
        # the entrypoint it audits; the failure itself becomes a
        # (never-silently-baselined) finding
        found.append(_registry.record(
            "GC000", site, f"auditor failed: {type(e).__name__}: {e}"))
    return found


# ---------------------------------------------------------------------------
# module-level report / ratchet surface
# ---------------------------------------------------------------------------

def findings():
    return _registry.findings()


def counts_by_key():
    return _registry.counts_by_key()


def watermarks():
    return _registry.watermarks()


def report():
    return _registry.report()


def assert_clean():
    """Raise GraphCheckError if any finding was recorded (message embeds
    the exemplars). The fault injector's final verdict."""
    rep = _registry.report()
    total = sum(rep["counts"].values())
    if total:
        lines = [f"  {f['site']} [{f['rule']}]: {f['message']}"
                 for f in rep["findings"]]
        raise GraphCheckError(
            f"graphcheck found {total} finding(s):\n" + "\n".join(lines))
    return rep


def load_baseline(path):
    import json

    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    if not isinstance(data, dict) or "counts" not in data:
        raise ValueError(f"{path}: not a graphcheck baseline "
                         "(missing 'counts')")
    return data


def write_baseline(path, counts, watermarks=None):
    """Deterministic (sorted-keys, newline-terminated) baseline dump —
    same shape as the tracelint/tpu-san ratchets, plus the per-site
    watermark section GC006 ratchets against."""
    import json

    data = {"version": 1, "tool": "graphcheck", "counts": dict(counts),
            "watermarks": {k: int(v)
                           for k, v in (watermarks or {}).items()}}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(data, f, indent=2, sort_keys=True)
        f.write("\n")


def new_counts(counts, baseline_counts):
    """{key: (count, baselined)} for keys whose count exceeds the
    baselined count — the ratchet's failing set."""
    return {k: (n, baseline_counts.get(k, 0))
            for k, n in sorted(counts.items())
            if n > baseline_counts.get(k, 0)}


def new_watermarks(current, baseline, slack=None):
    """{site: (bytes, baselined_bytes)} for sites whose estimated
    watermark regressed past the baselined value plus slack (default
    0.25, ``PADDLE_TPU_GRAPHCHECK_MEM_SLACK``). Sites with no baselined
    watermark pass (they enter the ratchet on the next
    ``--write-baseline``)."""
    if slack is None:
        slack = _env_float(_ENV_MEM_SLACK, 0.25)
    out = {}
    for site, cur in sorted(current.items()):
        base = baseline.get(site)
        if base is not None and cur > base * (1.0 + slack):
            out[site] = (int(cur), int(base))
    return out
