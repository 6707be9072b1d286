"""Collective-schedule auditor (paddle_tpu/analysis/commcheck): schedule
extraction from shard_map jaxprs and GSPMD HLO, line-number-free program
keys, baseline roundtrip + divergence naming, the zero-overhead-off
guard, the cross-host verifier over an in-memory store (clean cohort,
fingerprint divergence with agreeing blame on every host, entrypoint
ORDER divergence, gather timeout), the TrainWatchdog blame upgrade and
per-rejoin-epoch re-arm, and the comm_audit CLI exit-code contract —
including the acceptance proof that a planted scratch entrypoint with an
extra all-gather flips the CLI to exit 1 naming ``site::commcheck``.

Everything runs on the 8-virtual-device CPU platform conftest forces;
only the full-CLI dogfood pays a subprocess (slow-marked).
"""
import io
import json
import os
import subprocess
import sys
import threading

import pytest

import jax
import jax.numpy as jnp

from paddle_tpu.analysis import commcheck as cc
from jax import shard_map
from paddle_tpu.sharding import cpu_mesh, named_sharding, replicated, spec

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "comm_audit.py")
BASELINE = os.path.join(REPO, ".commcheck_baseline.json")


@pytest.fixture(autouse=True)
def _live_auditor():
    """Each test starts from an enabled, empty auditor with no verifier
    attached, and leaves the process back in the off state (other test
    files must not audit)."""
    cc.enable()
    cc.reset()
    cc.detach_store()
    yield
    cc.detach_store()
    cc.reset()
    cc.disable()


# ---------------------------------------------------------------------------
# schedule extraction: jaxpr (explicit collectives) + HLO (GSPMD-derived)
# ---------------------------------------------------------------------------

def test_shard_map_ppermute_schedule_ordered():
    """The ring-attention shape: two ppermutes inside a shard_map body
    must extract IN DISPATCH ORDER with their axis and permutation — the
    exact entries a reordered ring would churn."""
    mesh = cpu_mesh(tp=1, dp=8)
    fwd = [(i, (i + 1) % 8) for i in range(8)]
    bwd = [(i, (i - 1) % 8) for i in range(8)]

    def body(x):
        x = jax.lax.ppermute(x, "dp", fwd)
        return jax.lax.ppermute(x, "dp", bwd)

    f = shard_map(body, mesh=mesh, in_specs=(spec("dp"),),
                  out_specs=spec("dp"))
    jaxpr = jax.jit(f).trace(jnp.ones((8, 4))).jaxpr
    sched = cc.jaxpr_schedule(jaxpr)
    pp = [e for e in sched if e.startswith("jaxpr:ppermute@dp")]
    assert len(pp) == 2
    # order preserved: the forward ring (0 -> 1) before the backward
    # ring (0 -> 7), with the perm canonicalized into the entry
    assert "perm=((0, 1)" in pp[0] and "perm=((0, 7)" in pp[1]
    assert "float32" in pp[0]


def test_hlo_schedule_canonicalizes_kind_shape_groups_op():
    text = "\n".join([
        "  %ar = f32[8,4] all-reduce(f32[8,4] %p), "
        "replica_groups={{0,1,2,3},{4,5,6,7}}, to_apply=%add_12",
        "  %ag = (f32[8,8]) all-gather(f32[1,8] %x), "
        "replica_groups=[2,4]<=[8], dimensions={0}",
        "  %cp = f32[4] collective-permute(f32[4] %y), "
        "source_target_pairs={{0,1},{1,0}}",
        "  %mm = f32[8,8] dot(f32[8,4] %a, f32[4,8] %b)",
    ])
    sched = cc.hlo_schedule(text)
    assert sched == [
        # region-name numeric suffixes stripped: renames never churn
        "hlo:all-reduce f32[8,4] groups={{0,1,2,3},{4,5,6,7}} op=add",
        # the iota replica-group form scans through `<=`
        "hlo:all-gather f32[8,8] groups=[2,4]<=[8]",
        "hlo:collective-permute f32[4] groups={{0,1},{1,0}}",
    ]
    assert cc.hlo_schedule("") == [] and cc.hlo_schedule(None) == []


def test_gspmd_matmul_records_hlo_collectives_deterministically():
    """A contracted-dim-sharded matmul compiles to a GSPMD all-reduce;
    record_program must capture it and fingerprint it identically on a
    second extraction (the cross-host agreement property)."""
    mesh = cpu_mesh(tp=8)
    f = jax.jit(lambda a, b: a @ b,
                in_shardings=(named_sharding(mesh, spec(None, "tp")),
                              named_sharding(mesh, spec("tp", None))),
                out_shardings=replicated(mesh, 2))
    args = (jnp.ones((8, 8)), jnp.ones((8, 8)))
    p1 = cc.record_program("t.mm", jit_obj=f, args=args)
    p2 = cc.record_program("t.mm", jit_obj=f, args=args)
    assert p1 is not None and p2 is not None
    assert any(e.startswith("hlo:all-reduce") for e in p1.schedule)
    assert p1.fingerprint == p2.fingerprint and p1.key == p2.key
    assert p1.key in cc.schedules() and cc.errors() == {}


def test_program_key_stable_and_aval_sensitive():
    a = (jnp.ones((2, 3)), jnp.zeros((4,), jnp.int32))
    assert cc.program_key("engine.step", a) == \
        cc.program_key("engine.step", a)
    site, digest = cc.program_key("engine.step", a).split("::")
    assert site == "engine.step" and len(digest) == 8
    assert cc.program_key("engine.step", (jnp.ones((2, 4)),)) != \
        cc.program_key("engine.step", (jnp.ones((2, 3)),))


def test_extraction_failure_recorded_never_raised():
    bad = cc.record_program("t.bad", fn=lambda x: jnp.reshape(x, (7,)),
                            args=(jnp.ones(3),))
    assert bad is None
    assert "t.bad" in cc.errors()
    assert cc.schedules() == {}


# ---------------------------------------------------------------------------
# zero overhead off: the framework hooks reduce to one module-flag check
# ---------------------------------------------------------------------------

def test_off_records_nothing_through_the_aot_hook():
    from paddle_tpu.jit import aot

    cc.disable()
    assert not cc.enabled()
    before = dict(cc.registry().counters)
    aot.compile_jit(lambda x: x * 2,
                    (jax.ShapeDtypeStruct((4,), jnp.float32),),
                    tag="cc-off-probe")
    assert cc.registry().counters == before
    assert cc.schedules() == {} and cc.errors() == {}


def test_on_aot_hook_records_site_tagged_program():
    from paddle_tpu.jit import aot

    aot.compile_jit(lambda x: x * 2 + 1,
                    (jax.ShapeDtypeStruct((4,), jnp.float32),),
                    tag="cc-on-probe")
    scheds = cc.schedules()
    keys = [k for k in scheds if k.startswith("aot.cc-on-probe::")]
    assert len(keys) == 1
    assert scheds[keys[0]]["site"] == "aot.cc-on-probe"


# ---------------------------------------------------------------------------
# baseline roundtrip + divergence naming
# ---------------------------------------------------------------------------

def _sched(site, colls):
    return {"site": site, "fingerprint": cc.fingerprint_of(colls),
            "collectives": list(colls)}


def test_baseline_roundtrip_deterministic_and_validated(tmp_path):
    scheds = {"engine.step::aaaa0000": _sched("engine.step",
                                              ["jaxpr:psum@dp f32[2]"]),
              "aot.x::bbbb0000": _sched("aot.x", [])}
    p1, p2 = str(tmp_path / "b1.json"), str(tmp_path / "b2.json")
    cc.write_baseline(p1, scheds)
    cc.write_baseline(p2, dict(reversed(list(scheds.items()))))
    b1, b2 = open(p1).read(), open(p2).read()
    assert b1 == b2 and b1.endswith("\n")
    data = cc.load_baseline(p1)
    assert data["schedules"] == scheds and data["tool"] == "commcheck"
    bad = tmp_path / "bad.json"
    bad.write_text(json.dumps({"nope": 1}))
    with pytest.raises(ValueError):
        cc.load_baseline(str(bad))


def test_new_schedules_names_first_divergent_collective():
    base = {"engine.step::aaaa0000":
            _sched("engine.step", ["jaxpr:psum@dp f32[2]",
                                   "hlo:all-reduce f32[2] op=add"])}
    # clean: identical schedules ratchet silently
    assert cc.new_schedules(dict(base), base) == {}
    # an inserted all-gather is named WITH its position
    cur = {"engine.step::aaaa0000":
           _sched("engine.step", ["jaxpr:psum@dp f32[2]",
                                  "hlo:all-gather f32[2,8]",
                                  "hlo:all-reduce f32[2] op=add"])}
    fresh = cc.new_schedules(cur, base)
    (key, msgs), = fresh.items()
    assert key == "engine.step::commcheck"
    assert "position 1" in msgs[0] and "hlo:all-gather f32[2,8]" in msgs[0]
    # a DROPPED collective names the baseline entry the pod still expects
    cur = {"engine.step::aaaa0000": _sched("engine.step",
                                           ["jaxpr:psum@dp f32[2]"])}
    msgs = cc.new_schedules(cur, base)["engine.step::commcheck"]
    assert "missing" in msgs[0] and "all-reduce" in msgs[0]
    # an unbaselined program fails until deliberately ratcheted
    cur = dict(base)
    cur["aot.new::cccc0000"] = _sched("aot.new", ["hlo:all-gather f32[8]"])
    msgs = cc.new_schedules(cur, base)["aot.new::commcheck"]
    assert "unbaselined" in msgs[0] and "--write-baseline" in msgs[0]


# ---------------------------------------------------------------------------
# cross-host verifier over an in-memory store
# ---------------------------------------------------------------------------

class _MemStore:
    """The minimal coordination-store surface the verifier touches."""

    def __init__(self):
        self._d = {}
        self._mu = threading.Lock()

    def set(self, k, v):
        with self._mu:
            self._d[k] = v.encode() if isinstance(v, str) else v

    def get_nowait(self, k):
        with self._mu:
            return self._d.get(k)

    def keys(self, prefix=""):
        with self._mu:
            return [k for k in self._d if k.startswith(prefix)]

    def delete_key(self, k):
        with self._mu:
            return self._d.pop(k, None) is not None


def _prog(site, colls, key=None):
    return cc.Program(key or f"{site}::00000000", site,
                      cc.fingerprint_of(colls), list(colls))


def _verify_in_thread(v, prog, out):
    def run():
        try:
            v.verify(prog)
            out.append(None)
        except cc.CollectiveScheduleMismatchError as e:
            out.append(e)
    t = threading.Thread(target=run, daemon=True)
    t.start()
    return t


def test_verifier_clean_cohort_agrees_and_is_idempotent():
    store = _MemStore()
    va = cc._Verifier(store, "a", 2, timeout=10.0)
    vb = cc._Verifier(store, "b", 2, timeout=10.0)
    prog = _prog("engine.step", ["jaxpr:psum@dp f32[2]"])
    out = []
    t = _verify_in_thread(va, prog, out)
    vb.verify(prog)
    t.join(timeout=10.0)
    assert out == [None]
    assert cc.registry().counters["verified"] == 2
    assert cc.registry().counters["mismatches"] == 0
    # idempotent per program key: the SECOND dispatch pays nothing
    vb.verify(prog)
    assert cc.registry().counters["verified"] == 2
    assert store.get_nowait("/commcheck/0/mismatch") is None


def test_verifier_divergence_raises_typed_on_both_hosts():
    """Host b runs an extra all-gather at position 1: BOTH hosts must
    die typed, agreeing on the blamed host and the first divergent
    collective (1-vs-1 ties break toward the first host in sort order —
    the coordinator convention)."""
    store = _MemStore()
    va = cc._Verifier(store, "a", 2, timeout=10.0)
    vb = cc._Verifier(store, "b", 2, timeout=10.0)
    pa = _prog("engine.step", ["jaxpr:psum@dp f32[2]"],
               key="engine.step::11112222")
    pb = _prog("engine.step", ["jaxpr:psum@dp f32[2]",
                               "hlo:all-gather f32[2,8]"],
               key="engine.step::11112222")
    out = []
    t = _verify_in_thread(va, pa, out)
    with pytest.raises(cc.CollectiveScheduleMismatchError) as ei:
        vb.verify(pb)
    t.join(timeout=10.0)
    mine, theirs = ei.value, out[0]
    assert isinstance(theirs, cc.CollectiveScheduleMismatchError)
    for err in (mine, theirs):
        assert err.host == "b"
        assert err.site == "engine.step" and err.phase == "engine.step"
        assert err.index == 1
        assert err.first_divergent_collective == "hlo:all-gather f32[2,8]"
    assert cc.registry().counters["mismatches"] == 2
    # the record is published for late joiners / the watchdog
    assert store.get_nowait("/commcheck/0/mismatch") is not None


def test_verifier_entrypoint_order_divergence_names_both_sites():
    store = _MemStore()
    va = cc._Verifier(store, "a", 2, timeout=10.0)
    vb = cc._Verifier(store, "b", 2, timeout=10.0)
    out = []
    t = _verify_in_thread(va, _prog("engine.step", []), out)
    with pytest.raises(cc.CollectiveScheduleMismatchError) as ei:
        vb.verify(_prog("engine.eval", []))
    t.join(timeout=10.0)
    assert isinstance(out[0], cc.CollectiveScheduleMismatchError)
    for err in (ei.value, out[0]):
        assert err.host == "b"
        assert "order diverged" in err.first_divergent_collective
        assert "engine.eval" in str(err) and "engine.step" in str(err)


def test_verifier_gather_timeout_is_not_a_mismatch():
    """A peer that never publishes is a crash/wedge — the watchdog's
    jurisdiction; the verifier counts the timeout and RETURNS."""
    store = _MemStore()
    va = cc._Verifier(store, "a", 2, timeout=0.15)
    va.verify(_prog("engine.step", ["jaxpr:psum@dp f32[2]"]))
    assert cc.registry().counters["verify_timeouts"] == 1
    assert cc.registry().counters["mismatches"] == 0
    assert store.get_nowait("/commcheck/0/mismatch") is None


def test_attach_store_and_pending_mismatch_surface():
    store = _MemStore()
    rec = {"host": "b", "hosts": ["b"], "site": "engine.step",
           "expected_site": "engine.step", "index": 0,
           "collective": "hlo:all-gather f32[8] groups=[8]<=[8]",
           "fingerprint": "x", "expected_fingerprint": "y"}
    store.set("/commcheck/3/mismatch", json.dumps(rec))
    v = cc.attach_store(store, host="c", world_size=2, epoch=3)
    assert cc.verifier() is v and v.prefix() == "/commcheck/3"
    err = cc.pending_mismatch()
    assert isinstance(err, cc.CollectiveScheduleMismatchError)
    assert err.host == "b" and err.index == 0
    assert err.first_divergent_collective.startswith("hlo:all-gather")
    cc.detach_store()
    assert cc.verifier() is None and cc.pending_mismatch() is None


# ---------------------------------------------------------------------------
# TrainWatchdog integration: blame upgrade + per-rejoin-epoch re-arm
# ---------------------------------------------------------------------------

def test_watchdog_upgrades_wedge_blame_to_pending_mismatch():
    from paddle_tpu.distributed.train_guard import (TrainingStalledError,
                                                    TrainWatchdog)

    store = _MemStore()
    rec = {"host": "rank1", "hosts": ["rank1"], "site": "engine.step",
           "expected_site": "engine.step", "index": 2,
           "collective": "jaxpr:ppermute@cp float32[1, 8]",
           "fingerprint": "x", "expected_fingerprint": "y"}
    store.set("/commcheck/0/mismatch", json.dumps(rec))
    cc.attach_store(store, host="rank0", world_size=2)
    hits = []
    wd = TrainWatchdog(engine=None, timeout=0.1, host="rank0",
                       on_stall=hits.append)
    wd._stall(TrainingStalledError("dispatch wedged", host="rank0",
                                   phase="engine.step", elapsed=1.0))
    assert len(hits) == 1
    assert isinstance(hits[0], cc.CollectiveScheduleMismatchError)
    assert hits[0].host == "rank1" and hits[0].index == 2
    assert wd.stalled is hits[0]
    with pytest.raises(cc.CollectiveScheduleMismatchError):
        wd.raise_if_stalled()


def test_watchdog_dead_peer_blame_rearms_per_rejoin_epoch():
    """The PR-20 bugfix: a peer blamed once, revived (elastic relaunch
    under the same name), then wedged AGAIN must be reported as a FRESH
    event — the spent (host, epoch) count must not swallow it."""
    from paddle_tpu.distributed.train_guard import (TrainingStalledError,
                                                    TrainWatchdog,
                                                    recovery_counters)

    before = recovery_counters()["stalled_detections"]
    hits = []
    wd = TrainWatchdog(engine=None, timeout=0.1, host="me",
                       on_stall=hits.append)
    dead = TrainingStalledError("peer stopped heartbeating", host="peer",
                                phase="heartbeat", elapsed=1.0)
    wd._peers_dead(["train-peer", "train-me"])   # self filtered out
    wd._peers_dead(["train-peer"])               # spent: same epoch
    assert len(hits) == 1 and hits[0].host == "peer"
    assert wd.stalled is hits[0]
    wd._peers_recovered(["train-peer"])          # rejoin bumps the epoch
    assert wd.stalled is None                    # pending blame dropped
    wd._stall(dead)                              # second wedge: FRESH
    assert len(hits) == 2 and wd.stalled is dead
    assert recovery_counters()["stalled_detections"] - before == 2


# ---------------------------------------------------------------------------
# comm_audit CLI: exit-code contract + the acceptance plant
# ---------------------------------------------------------------------------

def _cli(argv=None):
    """comm_audit imported + main run in-process (argparse-level paths
    run no smokes)."""
    sys.path.insert(0, os.path.join(REPO, "tools"))
    try:
        import comm_audit
        return comm_audit, (None if argv is None else comm_audit.main(argv))
    finally:
        sys.path.pop(0)


def test_cli_usage_errors(tmp_path):
    assert _cli(["--smoke", "nope"])[1] == 2
    assert _cli(["--smoke", ""])[1] == 2
    bad = tmp_path / "corrupt.json"
    bad.write_text("{not json")
    assert _cli(["--baseline", str(bad)])[1] == 2
    assert _cli(["--baseline", str(tmp_path / "missing.json")])[1] == 2


def test_cli_changed_only_selector_and_noop_exit0(monkeypatch):
    comm_audit, _ = _cli()
    import tools.tpu_lint as tpu_lint

    # nothing changed -> exit 0 WITHOUT running any smoke
    monkeypatch.setattr(tpu_lint, "_changed_files",
                        lambda repo: ("base", []))
    assert comm_audit.main(["--changed-only"]) == 0
    # an inference-only change implicates exactly the decode smoke
    monkeypatch.setattr(
        tpu_lint, "_changed_files",
        lambda repo: ("base", ["paddle_tpu/inference/decode/engine.py"]))
    assert comm_audit.select_changed_smokes(comm_audit.SMOKES) == \
        (["decode"], ["paddle_tpu/inference/decode/engine.py"])
    # a change under analysis/ or tools/ implicates EVERYTHING
    monkeypatch.setattr(
        tpu_lint, "_changed_files",
        lambda repo: ("base", ["paddle_tpu/analysis/commcheck.py"]))
    sel, _ = comm_audit.select_changed_smokes(comm_audit.SMOKES)
    assert sel == list(comm_audit.SMOKES)
    # git failure fails SAFE toward auditing, never toward skipping
    monkeypatch.setattr(tpu_lint, "_changed_files", lambda repo: None)
    sel, rels = comm_audit.select_changed_smokes(comm_audit.SMOKES)
    assert sel == list(comm_audit.SMOKES) and rels is None


def test_cli_planted_scratch_entrypoint_flips_exit_1(monkeypatch):
    """Acceptance: a planted test-scratch entrypoint with an extra
    all-gather beyond the checked-in baseline flips the CLI to exit 1
    naming ``site::commcheck`` and the divergent collective — and the
    un-planted engine subset exits 0 against the same baseline."""
    from contextlib import redirect_stdout

    comm_audit, _ = _cli()
    real = comm_audit._SMOKE_FNS["engine"]

    def planted():
        real()
        mesh = cpu_mesh(tp=8)
        f = jax.jit(lambda x: x * 1.0,
                    in_shardings=(named_sharding(mesh, spec("tp")),),
                    out_shardings=replicated(mesh, 1))
        cc.record_program("test.scratch", jit_obj=f,
                          args=(jnp.ones((8,)),))

    monkeypatch.setitem(comm_audit._SMOKE_FNS, "engine", planted)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = comm_audit.main(["--smoke", "engine", "--format", "json"])
    assert rc == 1, out.getvalue()
    payload = json.loads(out.getvalue())
    (key, msgs), = payload["new"].items()
    assert key == "test.scratch::commcheck"
    assert "unbaselined" in msgs[0] and "all-gather" in msgs[0]
    assert payload["errors"] == {}

    monkeypatch.setitem(comm_audit._SMOKE_FNS, "engine", real)
    out = io.StringIO()
    with redirect_stdout(out):
        rc = comm_audit.main(["--smoke", "engine"])
    assert rc == 0, out.getvalue()


def test_checked_in_baseline_covers_required_entrypoints():
    """The committed contract, asserted without running a smoke: the
    baseline freezes the engine dense/fsdp/cp and decode entrypoints,
    every fingerprint matches its frozen schedule, and the schedules
    carry BOTH extraction levels (explicit shard_map ppermutes and
    GSPMD-derived HLO collectives)."""
    with open(BASELINE) as f:
        base = json.load(f)
    scheds = base["schedules"]
    sites = {v["site"] for v in scheds.values()}
    assert {"engine.step", "engine.multi", "engine.eval"} <= sites
    assert any(s.startswith("aot.decode") for s in sites)
    all_colls = [e for v in scheds.values() for e in v["collectives"]]
    assert any(e.startswith("jaxpr:ppermute@") for e in all_colls)
    assert any(e.startswith("hlo:all-gather") for e in all_colls)
    assert any(e.startswith("hlo:all-reduce") for e in all_colls)
    for key, v in scheds.items():
        assert v["fingerprint"] == cc.fingerprint_of(v["collectives"]), key


@pytest.mark.slow
def test_cli_subprocess_all_smokes_clean():
    """The CI-shaped invocation: a fresh process (the CLI pins its own
    platform/device-count env) runs every smoke and exits 0 against the
    checked-in baseline."""
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, CLI], capture_output=True,
                       text=True, timeout=600, env=env, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr
