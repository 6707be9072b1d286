"""Trace-safety linter (paddle_tpu/analysis/tracelint.py + tools/
tpu_lint.py): one unit per rule (bad code flagged, good twin clean),
trace-context discovery (decorators, partial, lax callers, lambdas,
same-module transitive callees), inline suppressions, the baseline
ratchet, CLI exit codes (0 clean / 1 new findings / 2 usage error), and
the dogfood run: the WHOLE framework must lint clean against the
checked-in baseline. Pure AST — nothing here compiles or traces."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from paddle_tpu.analysis import tracelint

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CLI = os.path.join(REPO, "tools", "tpu_lint.py")
BASELINE = os.path.join(REPO, ".tpu_lint_baseline.json")


def rules_of(src):
    return [f.rule for f in tracelint.lint_source(textwrap.dedent(src))]


# ---------------------------------------------------------------------------
# rule catalogue, one bad/good pair each
# ---------------------------------------------------------------------------

def test_tl001_wall_clock_under_trace():
    assert "TL001" in rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time()
        """)
    # host code: time.monotonic is fine anywhere outside a trace
    assert rules_of("""
        import time
        def f(x):
            return x * time.monotonic()
        """) == []
    # one suppression silences the line outright: TL010 must not pop up
    # on the same wall-clock call once TL001 is acknowledged
    assert rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time()  # tpu-lint: disable=TL001
        """) == []
    # bare from-imports reach the call site without the module prefix
    assert "TL001" in rules_of("""
        import jax
        from time import time
        @jax.jit
        def f(x):
            return x * time()
        """)
    assert "TL001" in rules_of("""
        import jax
        from time import monotonic as clock
        @jax.jit
        def f(x):
            return x * clock()
        """)


def test_tl002_host_rng_under_trace():
    assert "TL002" in rules_of("""
        import numpy as np, jax
        @jax.jit
        def f(x):
            return x + np.random.rand(3)
        """)
    assert "TL002" in rules_of("""
        import random
        from functools import partial
        import jax
        @partial(jax.jit, static_argnums=(1,))
        def f(x, n):
            return x + random.random()
        """)
    # from-imports reach the call site as a BARE name — the prefix
    # match alone would never see them
    assert rules_of("""
        from random import random
        from numpy.random import rand as nprand
        import jax
        @jax.jit
        def f(x):
            return x * random() + nprand()
        """).count("TL002") == 2
    # `from jax import random` is the CORRECT library — never flagged
    assert rules_of("""
        from jax import random
        import jax
        @jax.jit
        def f(key, x):
            return x + random.normal(key, x.shape)
        """) == []
    # a local binding shadowing the imported name is not the host RNG
    assert rules_of("""
        from random import random
        import jax
        @jax.jit
        def f(x, random):
            return x + random()
        """) == []


def test_tl003_concretization():
    assert "TL003" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            if bool(x > 0):
                return x
            return -x
        """)
    assert "TL003" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            return x.sum().item()
        """)
    # int() on a python literal is fine
    assert rules_of("""
        import jax
        @jax.jit
        def f(x):
            k = int("3")
            return x * k
        """) == []


def test_tl004_numpy_on_traced():
    assert "TL004" in rules_of("""
        import numpy as np, jax
        @jax.jit
        def f(x):
            return np.sum(x)
        """)
    # np on a host constant inside the trace is legitimate
    assert rules_of("""
        import numpy as np, jax
        @jax.jit
        def f(x):
            scale = np.sqrt(2.0)
            return x * scale
        """) == []


def test_tl005_closure_mutation():
    assert "TL005" in rules_of("""
        import jax
        seen = []
        @jax.jit
        def f(x):
            seen.append(x)
            return x
        """)
    assert "TL005" in rules_of("""
        import jax
        cache = {}
        @jax.jit
        def f(x):
            cache["k"] = x
            return x
        """)
    # mutating a LOCAL container is fine
    assert rules_of("""
        import jax
        @jax.jit
        def f(x):
            parts = []
            parts.append(x)
            return parts[0]
        """) == []
    # self/cls are parameters, not closed-over state: neither the
    # mutator-call nor the subscript-store branch may flag them
    assert rules_of("""
        import jax
        class M:
            @jax.jit
            def step(self, x):
                self.cache[0] = x
                self.items.append(x)
                return x
        """) == []


def test_tl006_print_under_trace():
    assert "TL006" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            print(x)
            return x
        """)
    # jax.debug.print is the sanctioned form
    assert rules_of("""
        import jax
        @jax.jit
        def f(x):
            jax.debug.print("x={x}", x=x)
            return x
        """) == []


def test_tl007_swallowed_exception():
    assert "TL007" in rules_of("""
        def f():
            try:
                work()
            except Exception:
                pass
        """)
    assert "TL007" in rules_of("""
        def f():
            try:
                work()
            except:
                return None
        """)
    # binding, re-raising, or narrowing all pass
    assert rules_of("""
        def f():
            try:
                work()
            except Exception as e:
                log(e)
            try:
                work()
            except Exception:
                raise RuntimeError("ctx")
            try:
                work()
            except ValueError:
                pass
        """) == []


def test_tl008_unhashable_static_arg():
    assert "TL008" in rules_of("""
        import jax
        def f(x, shape):
            return x.reshape(shape)
        g = jax.jit(f, static_argnums=(1,))
        out = g(x, [2, 3])
        """)
    assert rules_of("""
        import jax
        def f(x, shape):
            return x.reshape(shape)
        g = jax.jit(f, static_argnums=(1,))
        out = g(x, (2, 3))
        """) == []
    # bound method: static_argnums counts `self`, call-site args are
    # shifted one left — position 1 is the FIRST call-site arg
    method_src = """
        import jax
        from functools import partial
        class M:
            @partial(jax.jit, static_argnums=(1,))
            def f(self, cfg, x):
                return x
        m = M()
        out = m.f({t}, {x})
        """
    assert "TL008" in rules_of(method_src.format(t="[1, 2]", x="x"))
    assert rules_of(method_src.format(t='"cfg"', x="[1, 2]")) == []
    # an unrelated attribute call sharing a wrapped PLAIN function's
    # name must not match its static spec
    assert rules_of("""
        import jax
        def f(x, shape):
            return x.reshape(shape)
        g = jax.jit(f, static_argnums=(1,))
        out = other.g(x, [2, 3])
        """) == []


def test_tl009_fstring_over_traced():
    assert "TL009" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            key = f"val={x}"
            return x
        """)
    assert rules_of("""
        import jax
        @jax.jit
        def f(x):
            key = f"static={x.shape}"
            return x
        """) != [] or True  # .shape involves x: over-approx is acceptable


def test_jax_aliases_not_flagged_as_host_libs():
    """`from jax import random` / `import jax.numpy as np` bind names the
    host-lib rules pattern-match on — resolving the imports must exempt
    them (that code is already correct jax)."""
    assert rules_of("""
        import jax
        from jax import random
        @jax.jit
        def f(x, key):
            k1, k2 = random.split(key)
            return x + random.normal(k1, x.shape)
        """) == []
    assert rules_of("""
        import jax
        import jax.numpy as np
        @jax.jit
        def f(x):
            return np.sum(x)
        """) == []
    # the real host modules still flag
    assert "TL002" in rules_of("""
        import jax, random
        @jax.jit
        def f(x):
            return x + random.random()
        """)


def test_module_aliases_resolved():
    """`import time as t` / `import numpy as n` must not dodge the
    hazard rules — call sites resolve through the import alias map."""
    found = rules_of("""
        import time as t
        import jax
        @jax.jit
        def f(x):
            return x * t.time()

        def deadline():
            return t.time() + 5
        """)
    assert "TL001" in found and "TL010" in found
    assert rules_of("""
        import numpy as n
        import numpy.random as nr
        import random as rnd
        import jax
        @jax.jit
        def f(x):
            return x + n.random.rand(3) + nr.rand(3) + rnd.random()
        """).count("TL002") == 3
    assert "TL004" in rules_of("""
        import numpy as n
        import jax
        @jax.jit
        def f(x):
            return n.sum(x)
        """)
    assert "TL001" in rules_of("""
        from datetime import datetime as dt
        import jax
        @jax.jit
        def f(x):
            return x, dt.now()
        """)
    # aliases of jax modules stay exempt
    assert rules_of("""
        import jax
        import jax.numpy as n
        @jax.jit
        def f(x):
            return n.sum(x)
        """) == []


def test_lint_paths_overlapping_roots_dedup(tmp_path):
    sub = tmp_path / "pkg"
    sub.mkdir()
    f = sub / "m.py"
    f.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    once = tracelint.lint_paths([str(tmp_path)], relative_to=str(tmp_path))
    both = tracelint.lint_paths([str(tmp_path), str(sub)],
                                relative_to=str(tmp_path))
    assert len(once) == len(both) == 1  # overlapping roots: linted once


def test_tl000_parse_error_never_masked_by_baseline():
    """A syntax error gets its own rule id: a baselined TL007 for the
    same file must NOT absorb it (that would turn the whole file's
    ratchet off silently)."""
    fs = tracelint.lint_source("def broken(:\n")
    assert [f.rule for f in fs] == ["TL000"]
    masked = {f"<string>::TL007::<module>": 5}   # generous fake baseline
    assert tracelint.new_findings(fs, masked) == fs


def test_tl010_wall_clock_deadline():
    assert "TL010" in rules_of("""
        import time
        def f(timeout):
            deadline = time.time() + timeout
            return deadline
        """)
    assert rules_of("""
        import time
        def f(timeout):
            return time.monotonic() + timeout
        """) == []


# ---------------------------------------------------------------------------
# trace-context discovery
# ---------------------------------------------------------------------------

def test_transitive_same_module_callee_is_traced():
    src = """
        import time, jax
        def helper(x):
            return x * time.time()
        @jax.jit
        def f(x):
            return helper(x)
        """
    fs = tracelint.lint_source(textwrap.dedent(src))
    assert [f.rule for f in fs] == ["TL001"]
    assert fs[0].scope == "helper"


def test_lax_scan_function_arg_is_traced():
    assert "TL006" in rules_of("""
        import jax
        def step(carry, x):
            print(carry)
            return carry + x, x
        def run(xs):
            return jax.lax.scan(step, 0.0, xs)
        """)


def test_lax_data_args_do_not_taint_same_named_functions():
    """Only CALLABLE positions of a tracing caller mark functions as
    traced: scan's carry/xs and while_loop's init are data — a host
    function that happens to share their variable name stays host code."""
    assert rules_of("""
        import jax
        def setup():
            print("host side")
            return 0.0
        def run(xs, setup):
            def step(carry, x):
                return carry + x, x
            return jax.lax.scan(step, setup, xs)
        """) == []
    # while_loop: both arg 0 and arg 1 ARE callables; fori_loop: arg 2
    assert "TL006" in rules_of("""
        import jax
        def body(i, v):
            print(i)
            return v
        def run(v):
            return jax.lax.fori_loop(0, 8, body, v)
        """)
    assert "TL006" in rules_of("""
        import jax
        def keep_going(v):
            print(v)
            return v < 8
        def run(v):
            return jax.lax.while_loop(keep_going, lambda v: v + 1, v)
        """)
    # switch takes a LIST of branch callables at position 1
    assert "TL006" in rules_of("""
        import jax
        def branch_a(v):
            print(v)
            return v
        def run(i, v):
            return jax.lax.switch(i, [branch_a, lambda v: v], v)
        """)


def test_lambda_passed_to_tracing_caller():
    assert "TL001" in rules_of("""
        import time, jax
        def run(xs):
            return jax.lax.map(lambda x: x * time.time(), xs)
        """)


def test_def_after_call_site_still_traced():
    assert "TL001" in rules_of("""
        import time, jax
        g = None
        def install():
            global g
            g = jax.jit(body)
        def body(x):
            return x * time.time()
        """)


def test_untraced_host_code_is_not_flagged():
    assert rules_of("""
        import time, numpy as np
        def host(x):
            t = time.monotonic()
            print(t)
            return np.sum(x)
        """) == []


def test_nested_def_inside_traced_is_traced():
    assert "TL001" in rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            def inner(y):
                return y * time.time()
            return inner(x)
        """)


# ---------------------------------------------------------------------------
# suppressions + baseline ratchet
# ---------------------------------------------------------------------------

def test_inline_suppression():
    src = """
        import jax
        @jax.jit
        def f(x):
            print(x)  # tpu-lint: disable=TL006
            return x
        """
    assert rules_of(src) == []
    # disable=all and multi-rule forms; the `all` keyword is
    # case-insensitive like the rule ids
    assert rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time()  # tpu-lint: disable=all
        """) == []
    assert rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time()  # tpu-lint: disable=ALL
        """) == []
    # a plain-word reason after the rule id must not void the
    # suppression, and must not be mistaken for more rule tokens
    assert rules_of("""
        def f():
            try:
                work()
            except Exception:  # tpu-lint: disable=TL007 deliberate swallow
                pass
        """) == []
    # ...but 'all' buried in reason text is NOT a blanket suppression
    assert "TL006" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            print(x)  # tpu-lint: disable=TL009 silence all prints
            return x
        """)


def test_suppression_on_except_line():
    assert rules_of("""
        def f():
            try:
                work()
            except Exception:  # tpu-lint: disable=TL007 — deliberate
                pass
        """) == []


def test_suppression_marker_inside_string_does_not_suppress():
    """Only real comments suppress: a string literal containing the
    marker text must not silence findings on its line."""
    assert "TL006" in rules_of("""
        import jax
        @jax.jit
        def f(x):
            s = "# tpu-lint: disable=all"; print(x)
            return x
        """)
    assert "TL001" in rules_of("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time(), "# tpu-lint: disable=TL001"
        """)


def test_baseline_ratchet(tmp_path):
    bad = tmp_path / "mod.py"
    bad.write_text(textwrap.dedent("""
        import jax
        @jax.jit
        def f(x):
            print(x)
            return x
        """))
    findings = tracelint.lint_paths([str(bad)], relative_to=str(tmp_path))
    assert [f.rule for f in findings] == ["TL006"]
    bl = tmp_path / "baseline.json"
    tracelint.write_baseline(str(bl), findings)
    counts = tracelint.load_baseline(str(bl))
    # frozen: same findings are not "new"
    assert tracelint.new_findings(findings, counts) == []
    # a SECOND print in the same scope exceeds the count: both reported
    bad.write_text(bad.read_text().replace(
        "    return x", "    print(x)\n    return x"))
    worse = tracelint.lint_paths([str(bad)], relative_to=str(tmp_path))
    assert len(tracelint.new_findings(worse, counts)) == 2


def test_non_utf8_source_handled(tmp_path):
    """PEP 263 coding cookies are honored; undecodable bytes become a
    TL000 finding instead of an unhandled traceback mid-ratchet-run."""
    ok = tmp_path / "latin.py"
    ok.write_bytes(b"# -*- coding: latin-1 -*-\ns = '\xff'\nx = 1\n")
    assert tracelint.lint_file(str(ok)) == []
    broken = tmp_path / "broken.py"
    broken.write_bytes(b"x = 1\ns = '\xff'\n")
    assert [f.rule for f in tracelint.lint_file(str(broken))] == ["TL000"]


def test_tl000_is_never_baselined(tmp_path):
    """--write-baseline must not freeze a parse error, and a hand-edited
    baseline entry must not absorb one: a broken file yields ONLY TL000,
    so baselining it would hide every real finding in that file."""
    bad = tmp_path / "broken.py"
    bad.write_text("def f(:\n")
    findings = tracelint.lint_paths([str(bad)], relative_to=str(tmp_path))
    assert [f.rule for f in findings] == ["TL000"]
    bl = tmp_path / "b.json"
    tracelint.write_baseline(str(bl), findings)
    assert tracelint.load_baseline(str(bl)) == {}
    forged = {findings[0].key: 5}
    assert tracelint.new_findings(findings, forged) == findings


def test_baseline_is_deterministic(tmp_path):
    bad = tmp_path / "m.py"
    bad.write_text("try:\n    x = 1\nexcept Exception:\n    pass\n")
    fs = tracelint.lint_paths([str(bad)], relative_to=str(tmp_path))
    p1, p2 = tmp_path / "b1.json", tmp_path / "b2.json"
    tracelint.write_baseline(str(p1), fs)
    tracelint.write_baseline(str(p2), list(reversed(fs)))
    assert p1.read_text() == p2.read_text()
    assert p1.read_text().endswith("\n")


# ---------------------------------------------------------------------------
# CLI contract (subprocess; cheap — AST only, no jax import in the tool)
# ---------------------------------------------------------------------------

def _cli(*args):
    return subprocess.run([sys.executable, CLI, *args],
                          capture_output=True, text=True, timeout=120,
                          cwd=REPO)


def test_cli_dotted_package_resolves_without_importing(tmp_path):
    """--package paddle_tpu.jit must lint the subpackage WITHOUT
    importing paddle_tpu (find_spec on a dotted name executes the
    parent — seconds of jax startup and it runs the code being linted;
    on a jax-less box the package would misreport as unresolvable)."""
    r = _cli("--package", "paddle_tpu.jit")
    assert r.returncode == 0, r.stdout + r.stderr
    probe = (
        "import importlib.util, sys\n"
        f"spec = importlib.util.spec_from_file_location('tl', {CLI!r})\n"
        "m = importlib.util.module_from_spec(spec)\n"
        "spec.loader.exec_module(m)\n"
        "p = m._resolve_package('paddle_tpu.jit')\n"
        "assert p and p.replace('\\\\', '/').endswith("
        "'paddle_tpu/jit'), p\n"
        "assert m._resolve_package('paddle_tpu.device').endswith("
        "'device.py')\n"
        "assert m._resolve_package('paddle_tpu.no_such_mod') is None\n"
        "assert 'paddle_tpu' not in sys.modules, 'parent was imported'\n"
        "assert 'jax' not in sys.modules, 'jax was imported'\n")
    r = subprocess.run([sys.executable, "-c", probe], capture_output=True,
                       text=True, timeout=120, cwd=REPO)
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_violation_in_scratch_file_exits_1_with_rule_id(tmp_path):
    scratch = tmp_path / "scratch.py"
    scratch.write_text(textwrap.dedent("""
        import time, jax
        @jax.jit
        def f(x):
            return x * time.time()
        """))
    r = _cli("--paths", str(scratch), "--no-baseline", "--format", "json")
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert payload["new_count"] == 1
    assert payload["new"][0]["rule"] == "TL001"


def test_cli_write_baseline_count_excludes_tl000(tmp_path):
    """The reported count must match what was actually written: TL000
    entries are filtered from the file, so they must not be counted —
    and the dropped parse error must be surfaced, not silent."""
    (tmp_path / "broken.py").write_text("def f(:\n")
    (tmp_path / "real.py").write_text(
        "try:\n    x = 1\nexcept Exception:\n    pass\n")
    bl = tmp_path / "b.json"
    r = _cli("--paths", str(tmp_path), "--write-baseline",
             "--baseline", str(bl))
    assert r.returncode == 0, r.stdout + r.stderr
    assert "wrote 1 finding(s)" in r.stderr
    assert "NOT baselined" in r.stderr and "TL000" in r.stderr
    assert len(json.loads(bl.read_text())["counts"]) == 1


def test_cli_clean_file_exits_0(tmp_path):
    ok = tmp_path / "ok.py"
    ok.write_text("def f(x):\n    return x + 1\n")
    r = _cli("--paths", str(ok), "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr


def test_cli_usage_errors_exit_2(tmp_path):
    assert _cli("--package", "no_such_pkg_xyz").returncode == 2
    assert _cli("--paths", str(tmp_path / "missing.py")).returncode == 2
    assert _cli().returncode == 2                      # nothing to lint
    f = tmp_path / "f.py"
    f.write_text("x = 1\n")
    assert _cli("--paths", str(f), "--baseline",
                str(tmp_path / "nope.json")).returncode == 2
    # corrupt baseline
    bad = tmp_path / "bad.json"
    bad.write_text("{not json")
    assert _cli("--paths", str(f), "--baseline", str(bad)).returncode == 2


# ---------------------------------------------------------------------------
# --changed-only: the sub-second pre-commit loop
# ---------------------------------------------------------------------------

_VIOLATION = ("import time, jax\n"
              "@jax.jit\n"
              "def f(x):\n"
              "    return x * time.time()\n")


def _scratch_repo(tmp_path):
    def git(*args):
        r = subprocess.run(
            ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
            cwd=str(tmp_path), capture_output=True, text=True, timeout=30)
        assert r.returncode == 0, r.stderr
        return r.stdout

    git("init", "-q")
    (tmp_path / "clean.py").write_text("def f(x):\n    return x + 1\n")
    (tmp_path / "dirty.py").write_text(_VIOLATION)
    git("add", "-A")
    git("commit", "-qm", "seed")
    return git


def test_cli_changed_only_lints_only_touched_files(tmp_path):
    """The restriction proof: a committed violation in an UNTOUCHED file
    neither fails nor pollutes a --changed-only run; touching a file
    with a violation flips it to exit 1 with the rule id; untracked
    files count as changed."""
    _scratch_repo(tmp_path)
    # nothing changed since the merge-base -> trivially clean, even
    # though dirty.py (untouched) holds a TL001
    r = _cli("--paths", str(tmp_path), "--changed-only", "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s), 0 total" in r.stdout
    # touch only the clean file -> still clean
    (tmp_path / "clean.py").write_text("def f(x):\n    return x + 2\n")
    r = _cli("--paths", str(tmp_path), "--changed-only", "--no-baseline")
    assert r.returncode == 0, r.stdout + r.stderr
    # touch the violating file -> exit 1 naming the rule
    (tmp_path / "dirty.py").write_text(_VIOLATION + "\nY = 2\n")
    r = _cli("--paths", str(tmp_path), "--changed-only", "--no-baseline",
             "--format", "json")
    assert r.returncode == 1, r.stdout + r.stderr
    payload = json.loads(r.stdout)
    assert [f["rule"] for f in payload["new"]] == ["TL001"]
    # an untracked file is "changed" too
    (tmp_path / "dirty.py").write_text(_VIOLATION)   # restore
    subprocess.run(["git", "checkout", "--", "."], cwd=str(tmp_path))
    (tmp_path / "fresh.py").write_text(_VIOLATION)
    r = _cli("--paths", str(tmp_path), "--changed-only", "--no-baseline")
    assert r.returncode == 1, r.stdout + r.stderr
    assert "fresh.py" in r.stdout


def test_cli_changed_only_respects_baseline_for_changed_files(tmp_path):
    """Exit-code contract unchanged: a baselined violation in a touched
    file stays suppressed; a NEW one in the same file fails."""
    _scratch_repo(tmp_path)
    bl = tmp_path / "bl.json"
    r = _cli("--paths", str(tmp_path), "--write-baseline",
             "--baseline", str(bl))
    assert r.returncode == 0
    (tmp_path / "dirty.py").write_text(_VIOLATION + "Y = 2\n")  # benign
    r = _cli("--paths", str(tmp_path), "--changed-only",
             "--baseline", str(bl))
    assert r.returncode == 0, r.stdout + r.stderr
    (tmp_path / "dirty.py").write_text(_VIOLATION + _VIOLATION)
    r = _cli("--paths", str(tmp_path), "--changed-only",
             "--baseline", str(bl))
    assert r.returncode == 1, r.stdout + r.stderr


def test_cli_changed_only_usage_errors(tmp_path):
    _scratch_repo(tmp_path)
    # unresolvable base ref
    assert _cli("--paths", str(tmp_path), "--changed-only", "--base",
                "no/such/ref", "--no-baseline").returncode == 2
    # a partial lint must never regenerate the full baseline
    assert _cli("--paths", str(tmp_path), "--changed-only",
                "--write-baseline").returncode == 2
    # outside any git repo
    bare = tmp_path / "bare"
    bare.mkdir()
    (bare / "x.py").write_text("x = 1\n")
    env = dict(os.environ)
    env["GIT_CEILING_DIRECTORIES"] = str(tmp_path)
    r = subprocess.run(
        [sys.executable, CLI, "--paths", str(bare), "--changed-only",
         "--no-baseline"], capture_output=True, text=True, timeout=120,
        cwd=str(bare), env=env)
    assert r.returncode == 2, r.stdout + r.stderr


# ---------------------------------------------------------------------------
# TL012: raw threading-lock construction (named locks are lockcheck's and
# tpu-san's visibility contract)
# ---------------------------------------------------------------------------

def test_tl012_raw_threading_ctors_flagged():
    src = """
        import threading
        a = threading.Lock()
        b = threading.RLock()
        c = threading.Condition()
    """
    assert rules_of(src).count("TL012") == 3


def test_tl012_alias_aware():
    # module alias and from-import (with as-alias) both resolve
    assert rules_of("""
        import threading as t
        mu = t.Lock()
    """).count("TL012") == 1
    assert rules_of("""
        from threading import Lock as L, Condition
        a = L()
        b = Condition()
    """).count("TL012") == 2


def test_tl012_good_twins_not_flagged():
    # the named constructors, and same-named ctors from OTHER modules
    src = """
        import multiprocessing
        from paddle_tpu.analysis import locks
        a = locks.new_lock("subsystem.name")
        b = locks.new_condition("subsystem.name")
        c = multiprocessing.Lock()
        d = multiprocessing.RLock()
    """
    assert "TL012" not in rules_of(src)


def test_tl012_suppression_and_authority_exemption():
    src = ("import threading\n"
           "mu = threading.Lock()  # tpu-lint: disable=TL012\n")
    assert "TL012" not in [f.rule for f in tracelint.lint_source(src)]
    # the analysis package is the lock authority: its own raw primitives
    # (locks.py off-path, the checkers' self-guards) are exempt
    raw = "import threading\nmu = threading.Lock()\n"
    exempt = tracelint.lint_source(
        raw, path="paddle_tpu/analysis/lockcheck.py")
    assert "TL012" not in [f.rule for f in exempt]
    flagged = tracelint.lint_source(raw, path="paddle_tpu/flags.py")
    assert "TL012" in [f.rule for f in flagged]


def test_tl012_legacy_baseline_frozen():
    """The legacy raw-lock sites are baselined (burn down, never grow):
    14 at introduction, 7 after the PR-20 tranche (flags, core/monitor,
    fleet/elastic, p2p, rpc onto the named constructors) — and the
    checked-in TL011 ratchet keeps shrinking: 58 at introduction, 43
    after the collective/misc_api migration, 25 after the
    pipeline/data_parallel tranche, ≤15 after the
    moe/context_parallel tranche."""
    with open(BASELINE) as f:
        counts = json.load(f)["counts"]
    tl012 = {k: v for k, v in counts.items() if "::TL012::" in k}
    assert 0 < sum(tl012.values()) <= 7    # legacy sites only shrink...
    # the PR-20 tranche is gone from the baseline for good
    for rel in ("paddle_tpu/flags.py", "paddle_tpu/core/monitor.py",
                "paddle_tpu/distributed/fleet/elastic.py",
                "paddle_tpu/distributed/p2p.py",
                "paddle_tpu/distributed/rpc.py"):
        assert f"{rel}::TL012::<module>" not in tl012, rel
    tl011 = sum(v for k, v in counts.items() if "::TL011::" in k)
    assert tl011 == 0                      # ...and TL011 burned down
    assert not any("collective.py::TL011" in k or "misc_api.py::TL011" in k
                   for k in counts)
    # the PR-12 tranche: pipeline + data_parallel construct zero raw
    # NamedSharding/PartitionSpec now (they ask the factories)
    assert not any("pipeline.py::TL011" in k or
                   "data_parallel.py::TL011" in k for k in counts)
    # the PR-15 tranche: moe + context_parallel rebased onto the
    # factories (the all-to-all shard_map specs included)
    assert not any("moe.py::TL011" in k or
                   "context_parallel.py::TL011" in k for k in counts)
    # the PR-16 tranche retired the rule from the baseline outright:
    # ps + sequence_parallel + gpt_pipe were the last raw sites
    assert not any("::TL011::" in k for k in counts)


def test_tl011_migrated_files_are_clean():
    """Per-file clean assertions for the PR-15 (moe/context_parallel)
    and PR-16 (ps/sequence_parallel/gpt_pipe — the final tranche) TL011
    migrations — not just absent from the baseline, but zero findings in
    the live lint."""
    for rel in ("paddle_tpu/distributed/moe.py",
                "paddle_tpu/distributed/context_parallel.py",
                "paddle_tpu/distributed/ps.py",
                "paddle_tpu/distributed/sequence_parallel.py",
                "paddle_tpu/models/gpt_pipe.py"):
        fs = tracelint.lint_file(os.path.join(REPO, rel), rel)
        hits = [f for f in fs if f.rule == "TL011"]
        assert not hits, f"{rel}: {hits}"


def test_tl012_migrated_files_are_clean():
    """Per-file clean assertions for the PR-20 TL012 tranche (flags,
    core/monitor, fleet/elastic, p2p, rpc onto the locks.new_lock /
    new_condition named constructors) — not just absent from the
    baseline, but zero raw-primitive findings in the live lint."""
    for rel in ("paddle_tpu/flags.py",
                "paddle_tpu/core/monitor.py",
                "paddle_tpu/distributed/fleet/elastic.py",
                "paddle_tpu/distributed/p2p.py",
                "paddle_tpu/distributed/rpc.py"):
        fs = tracelint.lint_file(os.path.join(REPO, rel), rel)
        hits = [f for f in fs if f.rule == "TL012"]
        assert not hits, f"{rel}: {hits}"


# ---------------------------------------------------------------------------
# dogfood: the framework itself lints clean against the checked-in baseline
# ---------------------------------------------------------------------------

def test_framework_lints_clean_via_cli():
    """The CI-shaped invocation: exit 0 against the checked-in baseline.

    This single subprocess run proves both the exit-code contract and
    that the whole framework lints clean; an in-process duplicate would
    re-lint the full tree for no extra coverage (tier-1 budget is tight).
    """
    r = _cli("--package", "paddle_tpu")
    assert r.returncode == 0, r.stdout + r.stderr
    assert "0 new finding(s)" in r.stdout
