"""Auto-parallel planner: choose dp/mp/pp/sharding degrees from a cost
model.

Reference analog: the static auto-parallel Engine's Planner/completer +
cost model + auto-tuner
(python/paddle/distributed/auto_parallel/static/planner_v2.py,
static/cost/estimate_cost.py, auto_tuner/tuner.py) — which searches
process-mesh assignments against a cluster model.

TPU-native redesign: on a mesh runtime the *entire* search space is the
tuple of axis degrees (dp, mp, pp, sharding, sep) whose product is the
chip count — GSPMD derives everything below that. So the planner is an
explicit enumerate-and-score over divisor tuples:

- memory model: params + grads + optimizer moments + activations per
  chip under the candidate's sharding/tp/pp/sp splits (recompute
  discounts activations), must fit HBM;
- time model per step: MXU compute (6*N*tokens / peak) + DP/sharding
  gradient reduce-scatter+all-gather volume + TP per-block all-reduces
  + the PP bubble fraction — volumes priced over ICI bandwidth;
- the best-scoring feasible candidate becomes a Plan, which `apply()`
  turns into the hybrid mesh + engine kwargs.

Deliberately a closed-form analytic model (the reference simulates op
graphs): chip-count-scale search spaces are tiny, and the analytic form
makes every choice auditable in the Plan's rationale.
"""
from __future__ import annotations

import dataclasses
import math

import numpy as np

from ...device import CHIP_PEAKS

# The analytic model plans for a v5e cluster unless the caller passes its
# own `chip=`: HBM and bf16 peak come from the one sourced table.
DEFAULT_CHIP = dict(
    hbm_bytes=CHIP_PEAKS["TPU v5 lite"]["hbm_bytes"],
    peak_flops=CHIP_PEAKS["TPU v5 lite"]["bf16_flops"],
    ici_bandwidth=4.5e10,     # per-link bytes/s, one direction
)


@dataclasses.dataclass
class ModelStats:
    """What the cost model needs to know about the workload."""

    n_params: float
    num_layers: int
    hidden_size: int
    batch_size: int
    seq_len: int
    vocab_size: int = 0
    param_bytes: int = 2          # bf16 master-compute params
    grad_bytes: int = 2
    opt_state_bytes: int = 8      # adam: two fp32 moments
    act_bytes: int = 2
    recompute: bool = True

    @classmethod
    def from_model(cls, model, batch_size, seq_len, **kw):
        n = 0
        for _, p in model.named_parameters():
            n += int(np.prod(p.shape))
        cfg = getattr(model, "cfg", None)
        return cls(n_params=float(n),
                   num_layers=int(getattr(cfg, "num_layers", 1) or 1),
                   hidden_size=int(getattr(cfg, "hidden_size", 1) or 1),
                   vocab_size=int(getattr(cfg, "vocab_size", 0) or 0),
                   batch_size=batch_size, seq_len=seq_len, **kw)


@dataclasses.dataclass
class Candidate:
    dp: int
    mp: int
    pp: int
    sharding: int
    sep: int
    mem_per_chip: float
    step_time: float
    breakdown: dict
    microbatches: int = 1

    @property
    def degrees(self):
        return dict(dp=self.dp, mp=self.mp, pp=self.pp,
                    sharding=self.sharding, sep=self.sep)


class Plan:
    def __init__(self, best: Candidate, candidates, stats, chip):
        self.best = best
        self.candidates = candidates
        self.stats = stats
        self.chip = chip

    @property
    def degrees(self):
        return self.best.degrees

    @property
    def sharding_stage(self):
        return 2 if self.best.sharding > 1 else 0

    def apply(self):
        """Build the hybrid mesh + HCG for the chosen degrees."""
        from .. import topology as topo_mod
        mesh = topo_mod.build_mesh(**self.degrees)
        hcg = topo_mod.HybridCommunicateGroup(mesh=mesh)
        topo_mod.set_hybrid_communicate_group(hcg)
        return hcg

    def rationale(self):
        b = self.best
        lines = [
            f"chose dp={b.dp} mp={b.mp} pp={b.pp} sharding={b.sharding} "
            f"sep={b.sep} microbatches={b.microbatches}",
            f"est memory/chip: {b.mem_per_chip / 1e9:.2f} GB "
            f"(HBM {self.chip['hbm_bytes'] / 1e9:.0f} GB)",
            f"est step time: {b.step_time * 1e3:.1f} ms "
            f"({', '.join(f'{k}={v * 1e3:.1f}ms' for k, v in b.breakdown.items())})",
            f"rejected {len(self.candidates) - 1} feasible alternatives",
        ]
        return "\n".join(lines)


def _divisor_tuples(n, max_axes_vals):
    """All (dp, mp, pp, sharding, sep) with product == n, each axis
    bounded by max_axes_vals."""
    out = []
    axes = ["dp", "mp", "pp", "sharding", "sep"]

    def rec(i, remaining, cur):
        if i == len(axes) - 1:
            if remaining <= max_axes_vals[axes[i]]:
                out.append(cur + [remaining])
            return
        d = 1
        while d <= remaining:
            if remaining % d == 0 and d <= max_axes_vals[axes[i]]:
                rec(i + 1, remaining // d, cur + [d])
            d += 1

    rec(0, n, [])
    return [tuple(t) for t in out]


def _score(stats: ModelStats, chip, dp, mp, pp, sharding, sep,
           microbatches):
    """(mem_per_chip, step_time, breakdown) for one candidate."""
    N = stats.n_params
    data_ways = dp * sharding
    tokens = stats.batch_size * stats.seq_len

    if stats.batch_size % data_ways or stats.seq_len % sep:
        return None
    if stats.num_layers % pp:
        return None

    # ---- memory ------------------------------------------------------
    model_shard = mp * pp            # tensor+pipeline split of weights
    params = N * stats.param_bytes / model_shard
    params_resident = params / (sharding if sharding > 1 else 1)
    grads = N * stats.grad_bytes / model_shard / \
        (sharding if sharding > 1 else 1)
    opt = N * stats.opt_state_bytes / model_shard / \
        (sharding if sharding > 1 else 1)
    # activations: one transformer stack's worth for the local microbatch
    # (microbatches = gradient accumulation on non-pp plans, the 1F1B
    # chunking on pp plans — both bound live activations the same way)
    layers_local = stats.num_layers / pp
    mb = max(1, microbatches)
    act_tokens = tokens / data_ways / sep / mb
    act_factor = 2 if stats.recompute else 14  # remat keeps ~layer inputs
    acts = (act_tokens * stats.hidden_size * stats.act_bytes
            * layers_local * act_factor / mp)
    # pp keeps in-flight microbatch activations (1F1B: <= pp stages)
    if pp > 1:
        acts *= min(pp, mb)
    mem = params_resident + grads + opt + acts

    # ---- time --------------------------------------------------------
    bw = chip["ici_bandwidth"]
    flops = 6.0 * N * tokens
    n_chips = dp * mp * pp * sharding * sep
    t_compute = flops / (n_chips * chip["peak_flops"] * 0.5)

    # dp+sharding gradient sync: reduce-scatter + all-gather ring
    g_bytes = N * stats.grad_bytes / model_shard
    t_dp = (2.0 * (data_ways - 1) / max(data_ways, 1)) * g_bytes / bw \
        if data_ways > 1 else 0.0
    # tp: 2 all-reduces (attn + mlp) of activations per layer, fwd+bwd
    if mp > 1:
        a_bytes = (tokens / data_ways / sep) * stats.hidden_size \
            * stats.act_bytes
        t_tp = 4.0 * stats.num_layers * 2.0 * (mp - 1) / mp * a_bytes / bw
    else:
        t_tp = 0.0
    # sep: all-gather/reduce-scatter around attention blocks
    if sep > 1:
        a_bytes = (tokens / data_ways) * stats.hidden_size * stats.act_bytes
        t_sp = 2.0 * stats.num_layers * (sep - 1) / sep * a_bytes / bw
    else:
        t_sp = 0.0
    # pp bubble: (pp-1)/mb of the compute
    t_bubble = t_compute * (pp - 1) / mb if pp > 1 else 0.0

    t = t_compute + t_dp + t_tp + t_sp + t_bubble
    return mem, t, dict(compute=t_compute, dp=t_dp, tp=t_tp, sp=t_sp,
                        bubble=t_bubble)


def plan(model=None, stats: ModelStats | None = None, *, n_devices=None,
         batch_size=None, seq_len=None, chip=None, microbatches=4,
         max_mp=8, max_pp=None, allow_sep=False):
    """Search degree assignments; returns the best feasible Plan.

    Raises if nothing fits HBM (the reference tuner errors the same way
    when no distributed strategy satisfies memory)."""
    import jax

    chip = {**DEFAULT_CHIP, **(chip or {})}
    if n_devices is None:
        n_devices = jax.device_count()
    if stats is None:
        if model is None or batch_size is None or seq_len is None:
            raise ValueError("pass stats= or (model, batch_size, seq_len)")
        stats = ModelStats.from_model(model, batch_size, seq_len)

    bounds = dict(dp=n_devices, mp=max_mp,
                  pp=max_pp or stats.num_layers,
                  sharding=n_devices,
                  sep=(stats.seq_len if allow_sep else 1))
    feasible = []
    for dp, mp, pp, sharding, sep in _divisor_tuples(n_devices, bounds):
        # microbatch count joins the search: more accumulation chunks
        # bound activation memory at the cost of smaller per-step matmuls
        local_batch = stats.batch_size // max(dp * sharding, 1)
        mb = max(1, microbatches)
        while mb <= max(local_batch, 1):
            scored = _score(stats, chip, dp, mp, pp, sharding, sep, mb)
            if scored is not None:
                mem, t, br = scored
                if mem <= chip["hbm_bytes"] * 0.92:  # runtime headroom
                    feasible.append(Candidate(dp, mp, pp, sharding, sep,
                                              mem, t, br, mb))
                    break
            mb *= 2
    if not feasible:
        raise RuntimeError(
            f"no parallel plan fits {chip['hbm_bytes']/1e9:.0f} GB HBM on "
            f"{n_devices} chips for {stats.n_params/1e9:.2f}B params — "
            f"add chips, shrink the batch, or enable recompute")
    feasible.sort(key=lambda c: c.step_time)
    return Plan(feasible[0], feasible, stats, chip)


def auto_parallelize(model, optimizer=None, loss_fn=None, *, batch_size,
                     seq_len, chip=None, microbatches=4, **kw):
    """plan() + apply() + engine construction in one call (the reference
    Engine's `auto` mode: engine.prepare with strategy.auto_mode)."""
    from ..engine import parallelize as _parallelize

    p = plan(model=model, n_devices=None, batch_size=batch_size,
             seq_len=seq_len, chip=chip, microbatches=microbatches)
    hcg = p.apply()
    step = _parallelize(model, optimizer, loss_fn=loss_fn, mesh=hcg.mesh,
                        sharding_stage=p.sharding_stage, **kw)
    step.plan = p
    return step


# ---------------------------------------------------------------------------
# Measurement-driven tuner
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Measurement:
    candidate: Candidate
    step_time: float            # measured seconds (mean of pipelined iters)
    predicted: float            # analytic model's estimate


class TunedPlan(Plan):
    """A Plan whose winner was chosen by MEASURING candidates, not by
    trusting the analytic model (reference:
    distributed/auto_parallel/static/tuner/parallel_tuner.py:36 — the
    ParallelTuner compiles+profiles candidate dist programs; here a
    candidate is a mesh-degree tuple and 'profile' is timing the compiled
    train step on the live devices)."""

    def __init__(self, best, candidates, stats, chip, measurements,
                 calibration):
        super().__init__(best, candidates, stats, chip)
        self.measurements = measurements
        self.calibration = calibration      # measured/analytic time ratio

    def rationale(self):
        lines = [super().rationale(),
                 f"measured {len(self.measurements)} candidates "
                 f"(calibration x{self.calibration:.2f} vs analytic):"]
        for m in self.measurements:
            d = m.candidate.degrees
            lines.append(
                f"  dp={d['dp']} mp={d['mp']} pp={d['pp']} "
                f"sharding={d['sharding']}: measured "
                f"{m.step_time * 1e3:.1f} ms (analytic "
                f"{m.predicted * 1e3:.1f} ms)")
        return "\n".join(lines)


def _time_train_step(step, batch, warmup=1, iters=2):
    """Mean wall time of step.train_batch over `iters` pipelined steps.
    The loss readback (float(...)) is the fence, and it sits OUTSIDE the
    timed loop: the steps chain through donated state, so the last loss
    is ready only when every step has run, and the host keeps
    dispatching ahead of the device as it does in training."""
    import time

    def run():
        return (step.train_batch(*batch) if isinstance(batch, tuple)
                else step.train_batch(batch))

    for _ in range(warmup):
        float(run())
    t0 = time.perf_counter()
    loss = None
    for _ in range(iters):
        loss = run()
    float(loss)
    return (time.perf_counter() - t0) / iters


def tune(model, optimizer=None, loss_fn=None, *, batch_size, seq_len,
         sample_batch, top_k=3, chip=None, microbatches=4, n_devices=None,
         warmup=1, iters=2, stats=None, **kw):
    """Analytic plan() proposes top-k candidates; compile-and-time disposes.

    sample_batch: () -> batch (a Tensor or tuple of Tensors) accepted by the
    engine's train_batch for this model. Each candidate's mesh is built, the
    full train step compiled on the live devices (real chip, or the virtual
    CPU mesh under XLA_FLAGS=--xla_force_host_platform_device_count), and
    the fastest measured candidate wins. The measured/analytic ratio is
    returned as `calibration` so subsequent analytic-only planning can be
    scaled to this cluster (the reference ParallelTuner persists the same
    kind of profiled cost data).
    """
    from .. import topology as topo_mod
    from ..engine import parallelize as _parallelize

    p = plan(model=model, stats=stats, n_devices=n_devices,
             batch_size=batch_size, seq_len=seq_len, chip=chip,
             microbatches=microbatches)
    seen = set()
    cands = []
    for c in p.candidates:
        key = tuple(sorted(c.degrees.items()))
        if key not in seen:
            seen.add(key)
            cands.append(c)
        if len(cands) >= top_k:
            break

    prev_hcg = topo_mod.get_hybrid_communicate_group()
    # measuring runs REAL train steps: snapshot the live weights (and any
    # optimizer accumulators) so planning never mutates a trained model —
    # the reference ParallelTuner profiles on a throwaway program the same
    # way (parallel_tuner.py measures cloned dist_contexts)
    # snapshots live on the HOST: the engine donates device buffers into
    # the compiled step, so device-array references would be deleted by the
    # first measured step
    param_snap = {n: np.asarray(p._value)
                  for n, p in model.named_parameters()}
    buf_snap = {n: np.asarray(b._value) for n, b in model.named_buffers()}
    opt_state_attrs = {}
    if optimizer is not None:
        for attr, val in vars(optimizer).items():
            if isinstance(val, dict):
                opt_state_attrs[attr] = dict(val)
    measurements = []
    try:
        for c in cands:
            mesh = topo_mod.build_mesh(**c.degrees)
            hcg = topo_mod.HybridCommunicateGroup(mesh=mesh)
            topo_mod.set_hybrid_communicate_group(hcg)
            step = _parallelize(
                model, optimizer, loss_fn=loss_fn, mesh=mesh,
                sharding_stage=2 if c.sharding > 1 else 0, **kw)
            batch = sample_batch()
            t = _time_train_step(step, batch, warmup=warmup, iters=iters)
            measurements.append(Measurement(c, t, c.step_time))
            import jax.numpy as jnp
            for pname, param in model.named_parameters():
                param._value = jnp.asarray(param_snap[pname])
            for bname, buf in model.named_buffers():
                buf._value = jnp.asarray(buf_snap[bname])
            if optimizer is not None:
                for attr, val in opt_state_attrs.items():
                    setattr(optimizer, attr, dict(val))
    finally:
        topo_mod.set_hybrid_communicate_group(prev_hcg)

    measurements.sort(key=lambda m: m.step_time)
    best = measurements[0].candidate
    ratios = sorted(m.step_time / max(m.predicted, 1e-9)
                    for m in measurements)
    calibration = ratios[len(ratios) // 2]
    return TunedPlan(best, p.candidates, p.stats, p.chip, measurements,
                     calibration)


def auto_parallelize_tuned(model, optimizer=None, loss_fn=None, *,
                           batch_size, seq_len, sample_batch, top_k=3,
                           chip=None, warmup=1, iters=2, **kw):
    """tune() + apply() + fresh engine on the winning mesh."""
    from ..engine import parallelize as _parallelize

    tp = tune(model, optimizer, loss_fn=loss_fn, batch_size=batch_size,
              seq_len=seq_len, sample_batch=sample_batch, top_k=top_k,
              chip=chip, warmup=warmup, iters=iters, **kw)
    hcg = tp.apply()
    step = _parallelize(model, optimizer, loss_fn=loss_fn, mesh=hcg.mesh,
                        sharding_stage=tp.sharding_stage, **kw)
    step.plan = tp
    return step
