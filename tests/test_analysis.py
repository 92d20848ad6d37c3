"""repro.analysis: per-rule fixture corpus (true positive + clean pass),
inline suppression, baseline round-trip, and the self-check that src/
matches the committed baseline exactly."""
from pathlib import Path

from repro.analysis import baseline as bl
from repro.analysis.cli import main
from repro.analysis.core import Project, all_rules, run_rules
from repro.analysis.nk01_locks import LockDisciplineRule
from repro.analysis.nk02_clock import ClockDisciplineRule
from repro.analysis.nk03_tracing import TracingHygieneRule
from repro.analysis.nk04_registry import RegistryHygieneRule, spec_error

REPO = Path(__file__).resolve().parent.parent


def findings_for(rule, sources):
    return run_rules(Project.from_sources(sources), [rule])


# ---------------------------------------------------------------------------
# NK01 — lock discipline
# ---------------------------------------------------------------------------

NK01_BAD = '''
from repro.core.concurrency import guarded_by, make_lock

@guarded_by("_lock", "_entries", rank=10)
class Pool:
    def __init__(self):
        self._lock = make_lock("pool", 10)
        self._entries = {}

    def size(self):
        return len(self._entries)
'''

NK01_GOOD = '''
from repro.core.concurrency import guarded_by, make_lock

@guarded_by("_lock", "_entries", rank=10)
class Pool:
    def __init__(self):
        self._lock = make_lock("pool", 10)
        self._entries = {}

    def size(self):
        with self._lock:
            return len(self._entries)
'''


def test_nk01_flags_unlocked_access():
    fs = findings_for(LockDisciplineRule(), {"src/p.py": NK01_BAD})
    assert len(fs) == 1 and fs[0].rule == "NK01"
    assert "_entries" in fs[0].message


def test_nk01_clean_under_lock():
    assert findings_for(LockDisciplineRule(), {"src/p.py": NK01_GOOD}) == []


def test_nk01_comment_annotation_declares_guarded():
    src = '''
from repro.core.concurrency import make_lock

class Q:
    def __init__(self):
        self._lock = make_lock("q", 10)
        self._jobs = []      # guarded-by: _lock

    def bad(self):
        return self._jobs
'''
    fs = findings_for(LockDisciplineRule(), {"src/q.py": src})
    assert len(fs) == 1 and "_jobs" in fs[0].message


def test_nk01_holds_comment_exempts_helper():
    src = '''
from repro.core.concurrency import guarded_by, make_lock

@guarded_by("_lock", "_entries", rank=10)
class Pool:
    def __init__(self):
        self._lock = make_lock("pool", 10)
        self._entries = {}

    def _peek(self):   # holds: _lock
        return self._entries
'''
    assert findings_for(LockDisciplineRule(), {"src/p.py": src}) == []


def test_nk01_order_inversion():
    src = '''
from repro.core.concurrency import guarded_by, make_lock

@guarded_by("_outer", "_a", rank=20)
@guarded_by("_inner", "_b", rank=10)
class C:
    def __init__(self):
        self._outer = make_lock("o", 20)
        self._inner = make_lock("i", 10)
        self._a = 0
        self._b = 0

    def bad(self):
        with self._outer:
            with self._inner:
                self._b = 1
'''
    fs = findings_for(LockDisciplineRule(), {"src/c.py": src})
    assert len(fs) == 1 and "inversion" in fs[0].message


def test_nk01_nested_function_resets_held_state():
    src = NK01_GOOD.replace(
        "        with self._lock:\n            return len(self._entries)",
        "        with self._lock:\n"
        "            return lambda: len(self._entries)")
    fs = findings_for(LockDisciplineRule(), {"src/p.py": src})
    assert len(fs) == 1      # the closure may outlive the with-block


def test_nk01_foreign_private_access_is_flagged():
    sources = {"src/p.py": NK01_GOOD,
               "src/user.py": "def steal(pool):\n    return pool._entries\n"}
    fs = findings_for(LockDisciplineRule(), sources)
    assert len(fs) == 1
    assert fs[0].path == "src/user.py" and fs[0].severity == "warning"


# ---------------------------------------------------------------------------
# NK02 — clock discipline
# ---------------------------------------------------------------------------

NK02_BAD = '''
import time
from time import monotonic as mono

def f():
    return time.perf_counter() + mono()
'''


def test_nk02_flags_wall_clocks():
    fs = findings_for(ClockDisciplineRule(), {"src/f.py": NK02_BAD})
    assert len(fs) == 2 and all(f.rule == "NK02" for f in fs)


def test_nk02_sanctioned_modules_exempt():
    fs = findings_for(ClockDisciplineRule(),
                      {"src/repro/core/timing.py": NK02_BAD})
    assert fs == []


def test_nk02_clean_via_timing_primitives():
    src = '''
from repro.core.timing import Stopwatch

def f():
    sw = Stopwatch()
    return sw.elapsed()
'''
    assert findings_for(ClockDisciplineRule(), {"src/f.py": src}) == []


# ---------------------------------------------------------------------------
# NK03 — tracing hygiene
# ---------------------------------------------------------------------------

NK03_BAD = '''
import time
import jax

@jax.jit
def step(x):
    t0 = time.perf_counter()
    return float(x) + t0
'''

NK03_GOOD = '''
import jax
import jax.numpy as jnp

@jax.jit
def step(x):
    return jnp.sum(x) * 2
'''


def test_nk03_flags_impure_and_host_sync():
    msgs = [f.message for f in
            findings_for(TracingHygieneRule(), {"src/k.py": NK03_BAD})]
    assert len(msgs) == 2
    assert any("trace time" in m for m in msgs)
    assert any("host sync" in m for m in msgs)


def test_nk03_pure_jit_clean():
    assert findings_for(TracingHygieneRule(), {"src/k.py": NK03_GOOD}) == []


def test_nk03_pallas_kernel_is_a_root():
    src = '''
from jax.experimental import pallas as pl

def kernel(x_ref, o_ref):
    print("tracing")
    o_ref[...] = x_ref[...]

def call(x, shape):
    return pl.pallas_call(kernel, out_shape=shape)(x)
'''
    fs = findings_for(TracingHygieneRule(), {"src/k.py": src})
    assert len(fs) == 1 and "print" in fs[0].message


def test_nk03_transitive_helper_is_checked():
    src = '''
import random
import jax

def helper(x):
    return x * random.random()

@jax.jit
def step(x):
    return helper(x)
'''
    fs = findings_for(TracingHygieneRule(), {"src/k.py": src})
    assert len(fs) == 1 and "random.random" in fs[0].message


def test_nk03_flags_program_spans_and_counters():
    src = '''
import jax
from jax.experimental import pallas as pl
from repro.core import timing
from repro.core.timing import count

@jax.jit
def step(x):
    with timing.span("step"):
        return x * 2

def kernel(x_ref, o_ref):
    count("host_sync")
    o_ref[...] = x_ref[...]

def call(x, shape):
    return pl.pallas_call(kernel, out_shape=shape)(x)

def host(x):
    with timing.span("step"):
        timing.count("host_sync")
        return step(x)
'''
    fs = findings_for(TracingHygieneRule(), {"src/k.py": src})
    assert sorted(f.message.split("(")[0] for f in fs) == \
        ["count", "timing.span"]
    assert all("trace time" in f.message for f in fs)


def test_nk03_computed_static_argnums():
    src = '''
import jax

def f(x, n):
    return x

axis = [1]
g = jax.jit(f, static_argnums=axis)
'''
    fs = findings_for(TracingHygieneRule(), {"src/k.py": src})
    assert len(fs) == 1 and "static_argnums" in fs[0].message
    good = src.replace("static_argnums=axis", "static_argnums=(1,)")
    assert findings_for(TracingHygieneRule(), {"src/k.py": good}) == []


# ---------------------------------------------------------------------------
# NK04 — registry hygiene
# ---------------------------------------------------------------------------

NK04_BAD = '''
from repro.core.strategies import register_strategy

@register_strategy("dup")
class A:
    pass

@register_strategy("dup")
class B:
    pass
'''

NK04_GOOD = '''
from repro.core.strategies import get_strategy, register_strategy

@register_strategy("one")
class A:
    pass

@register_strategy("two")
class B:
    pass

def run():
    return get_strategy("one(k=2, mode='fast')")
'''


def test_nk04_duplicate_registration():
    fs = findings_for(RegistryHygieneRule(), {"src/r.py": NK04_BAD})
    assert len(fs) == 1 and "duplicate" in fs[0].message


def test_nk04_clean_registry():
    assert findings_for(RegistryHygieneRule(), {"src/r.py": NK04_GOOD}) == []


def test_nk04_shadowed_name_attribute():
    mismatch = '''
from repro.core.strategies import register_policy

@register_policy("real")
class P:
    name = "other"
'''
    fs = findings_for(RegistryHygieneRule(), {"src/r.py": mismatch})
    assert len(fs) == 1 and fs[0].severity == "error"
    redundant = mismatch.replace('name = "other"', 'name = "real"')
    fs = findings_for(RegistryHygieneRule(), {"src/r.py": redundant})
    assert len(fs) == 1 and fs[0].severity == "warning"


def test_nk04_bad_spec_literals():
    src = '''
from repro.core.strategies import get_strategy

def run(strategy="pool(k=)"):
    return get_strategy("switch pool(k=2)")
'''
    fs = findings_for(RegistryHygieneRule(), {"src/r.py": src})
    assert len(fs) == 2 and all("spec" in f.message for f in fs)


def test_spec_grammar():
    assert spec_error("pool") is None
    assert spec_error("pool(k=2, mode='fast')") is None
    assert spec_error("switch pool") is not None
    assert spec_error("pool(k=)") is not None
    assert spec_error("pool(2)") is not None          # positional
    assert spec_error("pool(k=f())") is not None      # non-literal


# ---------------------------------------------------------------------------
# suppression + baseline
# ---------------------------------------------------------------------------

def test_inline_allow_suppresses_only_named_rule():
    trailing = NK02_BAD.replace(
        "return time.perf_counter() + mono()",
        "return time.perf_counter() + mono()   # nk: allow[NK02]")
    assert findings_for(ClockDisciplineRule(), {"src/f.py": trailing}) == []
    wrong = NK02_BAD.replace(
        "return time.perf_counter() + mono()",
        "return time.perf_counter() + mono()   # nk: allow[NK01]")
    assert len(findings_for(ClockDisciplineRule(), {"src/f.py": wrong})) == 2


def test_standalone_allow_covers_next_line_only():
    src = '''
import time

def f():
    # nk: allow[NK02]: deliberate wall site
    t = time.perf_counter()
    return t + time.monotonic()
'''
    fs = findings_for(ClockDisciplineRule(), {"src/f.py": src})
    assert len(fs) == 1 and "monotonic" in fs[0].message


def test_baseline_round_trip_and_line_drift(tmp_path):
    fs = findings_for(ClockDisciplineRule(), {"src/f.py": NK02_BAD})
    path = tmp_path / "baseline.json"
    bl.save(path, fs)
    new, matched, stale = bl.diff(fs, bl.load(path))
    assert not new and not stale and len(matched) == len(fs)
    # unrelated edits shift line numbers; (path, rule, context) still keys
    drifted = findings_for(ClockDisciplineRule(),
                           {"src/f.py": "# header\n# comment\n" + NK02_BAD})
    new, matched, stale = bl.diff(drifted, bl.load(path))
    assert not new and not stale
    # fixing the finding makes its entry stale, never a failure; entries
    # are keyed (path, rule, context) so same-line findings share one
    new, matched, stale = bl.diff([], bl.load(path))
    assert not new and len(stale) == len({f.key() for f in fs})


def test_cli_exit_codes(tmp_path, monkeypatch):
    monkeypatch.chdir(tmp_path)
    bad = tmp_path / "bad.py"
    bad.write_text(NK02_BAD)
    good = tmp_path / "good.py"
    good.write_text("def f():\n    return 1\n")
    assert main([str(bad), "--no-baseline"]) == 1
    assert main([str(good), "--no-baseline"]) == 0
    # accepting via baseline turns the same findings green
    assert main([str(bad)]) == 1
    assert main([str(bad), "--write-baseline"]) == 0
    assert main([str(bad)]) == 0


# ---------------------------------------------------------------------------
# self-check: the shipped tree vs. the committed baseline
# ---------------------------------------------------------------------------

def test_src_matches_committed_baseline(monkeypatch):
    monkeypatch.chdir(REPO)
    project = Project.from_paths(["src"])
    findings = run_rules(project, all_rules())
    new, matched, stale = bl.diff(findings,
                                  bl.load(REPO / "analysis-baseline.json"))
    assert not new, "un-baselined findings:\n" + \
        "\n".join(f.render() for f in new)
    assert not stale, f"stale baseline entries: {stale}"
