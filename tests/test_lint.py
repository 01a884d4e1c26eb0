"""pslint (ps_pytorch_tpu/lint): one positive and one negative fixture
per rule, pragma suppression, baseline round-trip through --format json,
and the tier-1 repo gate: the package must be clean against the
committed baseline, so a new hot-path hazard fails CI here.

Pure-AST: no jax import happens inside the linter, so this file is fast
(<10 s including the full-package gate).
"""

import json
import subprocess
import sys
from pathlib import Path

import pytest

from ps_pytorch_tpu.lint import (
    apply_baseline,
    lint_paths,
    load_baseline,
    to_baseline_json,
)
from ps_pytorch_tpu.lint.axes import DEFAULT_AXES
from ps_pytorch_tpu.lint.core import lint_source

REPO = Path(__file__).resolve().parent.parent


def _lint(src: str, path: str = "snippet.py"):
    return lint_source(src, path, DEFAULT_AXES)


def _rules(findings):
    return [f.rule for f in findings]


# ------------------------------------------------------------------- PSL001

PSL001_POSITIVE = """
import jax
from jax.sharding import PartitionSpec as P

def agg(g):
    return jax.lax.psum(g, "workers")

def spec():
    return P("wrokers")
"""

PSL001_NEGATIVE = """
import jax
from ps_pytorch_tpu.parallel import WORKER_AXIS
from jax.sharding import PartitionSpec as P

def agg(g):
    return jax.lax.psum(g, WORKER_AXIS)

def spec():
    return P(WORKER_AXIS, None)
"""


def test_psl001_flags_literal_and_unknown_axis():
    findings = _lint(PSL001_POSITIVE)
    assert _rules(findings) == ["PSL001", "PSL001"]
    assert "WORKER_AXIS" in findings[0].message  # known axis -> use constant
    assert "unknown mesh axis 'wrokers'" in findings[1].message  # typo


def test_psl001_constants_are_clean():
    assert _lint(PSL001_NEGATIVE) == []


# ------------------------------------------------------------------- PSL002

PSL002_POSITIVE = """
import jax

def hot_loop(batches, f):
    out = []
    for b in batches:
        step = jax.jit(f)          # jit in a loop
        out.append(jax.jit(lambda x: x + 1)(b))  # lambda + one-shot
    return out
"""

PSL002_NEGATIVE = """
import jax

def build(f):
    step = jax.jit(f)

    def run(batches):
        return [step(b) for b in batches]

    return run
"""


def test_psl002_flags_loop_lambda_and_oneshot():
    rules = _rules(_lint(PSL002_POSITIVE))
    # jit-in-loop (x2: both calls are inside the loop), jit-on-lambda,
    # and jit(...)(...) one-shot in the loop
    assert rules.count("PSL002") >= 3


def test_psl002_hoisted_jit_is_clean():
    assert _lint(PSL002_NEGATIVE) == []


def test_psl002_one_shot_outside_loop_is_clean():
    # compiling once and calling once is not a recompilation hazard —
    # binding the callable first would change nothing
    src = "import jax\n\ndef f(g, x):\n    return jax.jit(g)(x)\n"
    assert _lint(src) == []


def test_psl002_comprehensions_are_loops():
    src = (
        "import jax\n\ndef f(g, batches):\n"
        "    return [jax.jit(g)(b) for b in batches]\n"
    )
    rules = _rules(_lint(src))
    assert rules.count("PSL002") == 2  # jit-in-loop + per-iteration one-shot


def test_psl002_loop_headers_and_else_run_once():
    # a for's iterable and a loop's else-body evaluate exactly once
    src = (
        "import jax\n\ndef f(g, batches, x):\n"
        "    for y in jax.jit(g)(batches):\n"
        "        pass\n"
        "    else:\n"
        "        z = jax.jit(g)(x)\n"
        "    return z\n"
    )
    assert _lint(src) == []


# ------------------------------------------------------------------- PSL003

PSL003_POSITIVE = """
import time
import numpy as np
import jax

side_channel = []

@jax.jit
def step(x):
    print("step!", x)
    t0 = time.time()
    noise = np.random.randn(4)
    side_channel.append(t0)
    return x + noise
"""

PSL003_NEGATIVE = """
import jax
import jax.numpy as jnp

@jax.jit
def step(x, key):
    acc = []
    for i in range(4):          # static unroll of a LOCAL list is fine
        acc.append(x * i)
    noise = jax.random.normal(key, x.shape)
    jax.debug.print("step {x}", x=x)
    return sum(acc) + noise
"""


def test_psl003_flags_impurity_in_traced_fn():
    rules = _rules(_lint(PSL003_POSITIVE))
    assert rules.count("PSL003") == 4  # print, time.time, np.random, append


def test_psl003_pure_traced_fn_is_clean():
    assert _lint(PSL003_NEGATIVE) == []


def test_psl003_scan_body_and_shard_map_are_traced():
    src = """
import jax

def outer(xs):
    def body(carry, x):
        print(x)
        return carry, x
    return jax.lax.scan(body, 0, xs)
"""
    assert _rules(_lint(src)) == ["PSL003"]


# ------------------------------------------------------------------- PSL004

PSL004_POSITIVE = """
import jax

def train(step, batches, state):
    for b in batches:
        state, metrics = step(state, b)
        m = jax.device_get(metrics)
        loss = float(metrics["loss"])
    return state
"""

PSL004_NEGATIVE = """
import jax

def train(step, batches, state, log_every=100):
    for i, b in enumerate(batches):
        state, metrics = step(state, b)
        if i % log_every == 0:
            metrics = jax.device_get(metrics)  # psl: sync-ok
            print(metrics["loss"])
    return state
"""


def test_psl004_flags_per_step_syncs_in_hot_module():
    rules = _rules(_lint(PSL004_POSITIVE, path="trainer.py"))
    assert rules == ["PSL004", "PSL004"]  # device_get + float(device value)


def test_psl004_only_applies_to_hot_modules():
    assert _lint(PSL004_POSITIVE, path="offline_eval.py") == []


def test_psl004_sync_ok_pragma_suppresses():
    assert _lint(PSL004_NEGATIVE, path="trainer.py") == []


def test_psl004_taint_is_flow_sensitive():
    """A periodic `metrics = jax.device_get(metrics)` behind a log guard
    must NOT launder the per-step float() that runs BEFORE it — the taint
    follows statement order, including the loop back-edge."""
    src = """
import jax

def train(step, batches, state, log_every=100):
    for i, b in enumerate(batches):
        state, metrics = step(state, b)
        loss = float(metrics["loss"])         # per-step sync: must flag
        if i % log_every == 0:
            metrics = jax.device_get(metrics)  # psl: sync-ok
    return state
"""
    findings = _lint(src, path="trainer.py")
    assert _rules(findings) == ["PSL004"]
    assert "float()" in findings[0].message


def test_psl004_real_trainer_is_windowed():
    """The production trainer keeps metrics on device between log windows;
    every intentional transfer carries the pragma."""
    findings = [
        f for f in lint_paths([str(REPO / "ps_pytorch_tpu" / "trainer.py")])
        if f.rule == "PSL004"
    ]
    assert findings == []


PSL004_TICK = """
import jax
import numpy as np

class Engine:
    def tick(self):
        pool, nxt = self._decode(self._pool)
        return np.asarray(jax.device_get(nxt))
"""


def test_psl004_serve_tick_is_a_hot_loop_body():
    """The serving engine's per-step entry point (tick) is a loop body
    by contract — its caller invokes it once per decode step — so a
    host fetch inside it flags even with the `while` in another
    function. Scope: THE serve engine module (a path-suffix entry in
    HOT_MODULES — an unrelated file that happens to be named engine.py
    is not captured)."""
    assert _rules(
        _lint(PSL004_TICK, path="ps_pytorch_tpu/serve/engine.py")
    ) == ["PSL004"]
    # a generic engine.py elsewhere, or any other module: out of scope
    assert _lint(PSL004_TICK, path="tools/engine.py") == []
    assert _lint(PSL004_TICK, path="pipeline.py") == []


def test_psl004_real_serve_engine_has_one_blessed_fetch():
    """The production request loop's ONLY host sync is the scheduler's
    fused [slots] token fetch, and it carries the pragma — any further
    per-token sync creeping into serve/ fails the gate."""
    findings = [
        f for f in lint_paths(
            [str(REPO / "ps_pytorch_tpu" / "serve")]
        )
        if f.rule in ("PSL002", "PSL004")
    ]
    assert findings == []
    src = (REPO / "ps_pytorch_tpu" / "serve" / "engine.py").read_text()
    assert src.count("# psl: sync-ok") == 1


# ------------------------------------------------------------------- PSL005

PSL005_POSITIVE = """
import jax

def make_train_step(f):
    return jax.jit(f, donate_argnums=(0, 1) if True else ())

def run(params, opt, tok):
    step = make_train_step(lambda p, o, t: (p, o))
    new_p, new_o = step(params, opt, tok)
    return params  # donated buffer read after the call
"""

PSL005_NEGATIVE = """
import jax

def make_train_step(f):
    return jax.jit(f, donate_argnums=(0, 1))

def run(params, opt, tok, n):
    step = make_train_step(lambda p, o, t: (p, o))
    for _ in range(n):
        params, opt = step(params, opt, tok)  # rebinds: safe
    return params

def run_undonated(params, opt, tok):
    step = make_train_step(lambda p, o, t: (p, o), donate=False)
    new_p, _ = step(params, opt, tok)
    return params  # not donated: safe
"""


def test_psl005_flags_read_after_donation():
    findings = [f for f in _lint(PSL005_POSITIVE) if f.rule == "PSL005"]
    assert len(findings) == 1
    assert "'params' read after being donated" in findings[0].message


def test_psl005_rebind_and_opt_out_are_clean():
    assert [f for f in _lint(PSL005_NEGATIVE) if f.rule == "PSL005"] == []


def test_psl005_loop_carries_donation_to_next_iteration():
    src = """
import jax

def make_train_step(f):
    return jax.jit(f, donate_argnums=(0,))

def run(state, batches):
    step = make_train_step(lambda s, b: s)
    for b in batches:
        new_state = step(state, b)  # `state` donated on iter 1, read on iter 2
    return new_state
"""
    findings = [f for f in _lint(src) if f.rule == "PSL005"]
    assert len(findings) >= 1


def test_psl005_factories_discovered_across_files(tmp_path):
    """A factory in one file, the unsafe call site in another: lint_paths
    links them (this is how tests calling parallel/ factories are checked)."""
    (tmp_path / "maker.py").write_text(
        "import jax\n"
        "def make_step(f):\n"
        "    return jax.jit(f, donate_argnums=(0,))\n"
    )
    (tmp_path / "caller.py").write_text(
        "from maker import make_step\n"
        "def go(state, b):\n"
        "    step = make_step(lambda s, b: s)\n"
        "    out = step(state, b)\n"
        "    return state\n"
    )
    findings = lint_paths([str(tmp_path)])
    assert [f.rule for f in findings] == ["PSL005"]


# ------------------------------------------------------------- pragmas / CLI

def test_blanket_ignore_pragma():
    src = 'import jax\n\ndef f(g):\n    return jax.lax.psum(g, "workers")  # psl: ignore\n'
    assert _lint(src) == []


def test_rule_scoped_ignore_pragma():
    src = (
        'import jax\n\ndef f(g):\n'
        '    return jax.lax.psum(g, "workers")  # psl: ignore[PSL001]\n'
    )
    assert _lint(src) == []
    src_wrong_rule = src.replace("PSL001", "PSL002")
    assert _rules(_lint(src_wrong_rule)) == ["PSL001"]


def test_rule_scoped_ignore_tolerates_spaced_bracket():
    """'# psl: ignore [PSL002]' must scope to PSL002 — never degrade to a
    blanket ignore because of the space before the bracket."""
    src = (
        'import jax\n\ndef f(g):\n'
        '    return jax.lax.psum(g, "workers")  # psl: ignore [PSL002]\n'
    )
    assert _rules(_lint(src)) == ["PSL001"]  # PSL001 still reported


def test_psl004_flags_while_test_sync():
    """A while-test re-runs every iteration: a host sync there is a
    per-step sync even at the top level of a function."""
    src = """
import jax

def train(step, state, b, metrics):
    while float(metrics["loss"]) > 0.1:
        state, metrics = step(state, b)
    return state
"""
    assert _rules(_lint(src, path="trainer.py")) == ["PSL004"]


def test_pragma_covers_multiline_statement():
    """A pragma after the closing paren of a formatter-wrapped call still
    suppresses a finding anchored to the call's first line."""
    src = (
        "import jax\n\ndef f(g):\n"
        "    return jax.lax.psum(\n"
        "        g,\n"
        '        "workers",\n'
        "    )  # psl: ignore[PSL001]\n"
    )
    assert _lint(src) == []


def test_pragma_in_string_is_not_a_pragma():
    src = (
        'import jax\n\ndef f(g):\n'
        '    s = " # psl: ignore"\n'
        '    return jax.lax.psum(g, "workers"), s\n'
    )
    assert _rules(_lint(src)) == ["PSL001"]


def test_pragma_on_decorator_line_suppresses_decorator_finding():
    """A PSL002 finding anchored to a decorator call (jit-in-loop via a
    decorated def) is suppressed by a pragma ON the decorator line."""
    base = (
        "import jax\n\n"
        "def build(cfgs):\n"
        "    out = []\n"
        "    for donate in cfgs:\n"
        "        @jax.jit(donate_argnums=(0,) if donate else ()){pragma}\n"
        "        def step(x):\n"
        "            return x\n"
        "        out.append(step)\n"
        "    return out\n"
    )
    assert _rules(_lint(base.format(pragma=""))) == ["PSL002"]
    assert _lint(base.format(pragma="  # psl: ignore[PSL002]")) == []


def test_pragma_covers_formatter_wrapped_decorator():
    """Decorators are expressions hanging off a compound statement, so
    they need their own pragma spans: a pragma after the closing paren of
    a wrapped decorator must reach the finding on its first line."""
    src = (
        "import jax\n\n"
        "def build(cfgs):\n"
        "    out = []\n"
        "    for donate in cfgs:\n"
        "        @jax.jit(\n"
        "            donate_argnums=(0,),\n"
        "        )  # psl: ignore[PSL002]\n"
        "        def step(x):\n"
        "            return x\n"
        "        out.append(step)\n"
        "    return out\n"
    )
    assert _lint(src) == []


def test_pragma_on_def_line_does_not_cover_decorator_finding():
    """The def header is a different line than the decorator: a pragma
    there must not silently widen to the decorator's finding."""
    src = (
        "import jax\n\n"
        "def build(cfgs):\n"
        "    out = []\n"
        "    for donate in cfgs:\n"
        "        @jax.jit(donate_argnums=(0,) if donate else ())\n"
        "        def step(x):  # psl: ignore[PSL002]\n"
        "            return x\n"
        "        out.append(step)\n"
        "    return out\n"
    )
    assert _rules(_lint(src)) == ["PSL002"]


def test_select_does_not_let_other_rules_pragma_leak(tmp_path):
    """One line, two rules, a pragma for one of them: selecting the
    OTHER rule must still report it — a selected-out rule must not
    consume (or widen) the pragma."""
    snippet = tmp_path / "hot.py"
    snippet.write_text(
        "import jax\n\ndef f():\n"
        '    return jax.jit(lambda x: jax.lax.psum(x, "wrokers"))'
        "  # psl: ignore[PSL002]\n"
    )
    cmd = [sys.executable, "-m", "ps_pytorch_tpu.lint", str(snippet),
           "--no-baseline", "--format", "json"]
    both = subprocess.run(cmd, capture_output=True, text=True,
                          cwd=str(REPO))
    assert both.returncode == 1
    assert [f["rule"] for f in json.loads(both.stdout)["new"]] == ["PSL001"]
    sel_psl001 = subprocess.run(cmd + ["--select", "PSL001"],
                                capture_output=True, text=True,
                                cwd=str(REPO))
    assert sel_psl001.returncode == 1
    assert [f["rule"] for f in json.loads(sel_psl001.stdout)["new"]] == [
        "PSL001"
    ]
    sel_psl002 = subprocess.run(cmd + ["--select", "PSL002"],
                                capture_output=True, text=True,
                                cwd=str(REPO))
    assert sel_psl002.returncode == 0, sel_psl002.stdout
    assert json.loads(sel_psl002.stdout)["new"] == []


def test_stale_counts_only_scanned_paths(tmp_path):
    """A baseline entry for a file OUTSIDE this run's scope is not
    'stale' — linting tools/ must not report the package's own entries
    as prunable just because their files were not scanned."""
    from ps_pytorch_tpu.lint import Finding

    scanned_dir = tmp_path / "scanned"
    scanned_dir.mkdir()
    hot = scanned_dir / "hot.py"
    hot.write_text("import jax\n\ndef f(x):\n    return x\n")
    baseline = tmp_path / "baseline.json"
    baseline.write_text(json.dumps(to_baseline_json([
        Finding("PSL001", str(hot), 1, 0, "m", "gone_line"),
        Finding("PSL001", "elsewhere/never_scanned.py", 1, 0, "m", "x"),
    ])))
    proc = subprocess.run(
        [sys.executable, "-m", "ps_pytorch_tpu.lint", str(scanned_dir),
         "--baseline", str(baseline), "--format", "json"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    stale = json.loads(proc.stdout)["stale"]
    assert [s["path"] for s in stale] == [str(hot)]


def test_linting_tools_reports_no_stale_package_entries():
    """The exact regression: `python -m ps_pytorch_tpu.lint tools/`
    against the committed baseline used to report the package's
    cli/evaluate_lm.py entries as '2 stale baseline entries' even though
    that file was never linted."""
    proc = subprocess.run(
        [sys.executable, "-m", "ps_pytorch_tpu.lint", "tools",
         "--baseline", "lint_baseline.json"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr
    assert "0 stale baseline entr" in proc.stdout


def test_cli_rejects_missing_path_and_select_write_combo(tmp_path):
    """A mistyped path must be a usage error (exit 2), never a clean exit
    that lints nothing; --select + --write-baseline would silently drop
    baseline entries for unselected rules."""
    cmd = [sys.executable, "-m", "ps_pytorch_tpu.lint"]
    bad = subprocess.run(cmd + ["no_such_dir_xyz"], capture_output=True,
                         text=True, cwd=str(REPO))
    assert bad.returncode == 2
    assert "no such file" in bad.stderr
    combo = subprocess.run(
        cmd + ["ps_pytorch_tpu", "--select", "PSL001", "--write-baseline",
               "--baseline", str(tmp_path / "b.json")],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert combo.returncode == 2
    assert not (tmp_path / "b.json").exists()
    notpy = subprocess.run(cmd + ["tools/lint.sh"], capture_output=True,
                           text=True, cwd=str(REPO))
    assert notpy.returncode == 2
    assert "not a python file" in notpy.stderr


def test_syntax_error_reported_as_psl000(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("def broken(:\n")
    findings = lint_paths([str(bad)])
    assert _rules(findings) == ["PSL000"]


# ------------------------------------------------------- baseline round-trip

def test_baseline_round_trips_through_json(tmp_path):
    """--format json output's `findings` array IS a valid baseline: feeding
    it back makes the same run exit 0 with everything baselined."""
    snippet = tmp_path / "hot.py"
    snippet.write_text(
        'import jax\n\ndef f(g):\n    return jax.lax.psum(g, "workers")\n'
    )
    env_cmd = [sys.executable, "-m", "ps_pytorch_tpu.lint", str(snippet)]
    first = subprocess.run(
        env_cmd + ["--format", "json", "--no-baseline"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert first.returncode == 1
    payload = json.loads(first.stdout)
    assert [f["rule"] for f in payload["new"]] == ["PSL001"]

    baseline_file = tmp_path / "baseline.json"
    baseline_file.write_text(json.dumps(payload))  # findings key reused as-is
    second = subprocess.run(
        env_cmd + ["--baseline", str(baseline_file)],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert second.returncode == 0, second.stdout + second.stderr
    assert "1 baselined" in second.stdout


def test_baseline_matches_on_text_not_line_numbers():
    from ps_pytorch_tpu.lint import Finding

    current = [Finding("PSL001", "a.py", 42, 0, "msg", 'psum(g, "workers")')]
    moved = [Finding("PSL001", "a.py", 99, 0, "msg", 'psum(g, "workers")')]
    new, matched, stale = apply_baseline(current, moved)
    assert new == [] and len(matched) == 1 and stale == []


def test_stale_baseline_entries_are_reported():
    from ps_pytorch_tpu.lint import Finding

    baseline = [Finding("PSL001", "a.py", 1, 0, "msg", "gone_line")]
    new, matched, stale = apply_baseline([], baseline)
    assert new == [] and matched == [] and len(stale) == 1


def test_to_baseline_and_load_round_trip(tmp_path):
    from ps_pytorch_tpu.lint import Finding

    f = Finding("PSL002", "b.py", 7, 3, "m", "jax.jit(lambda x: x)")
    p = tmp_path / "b.json"
    p.write_text(json.dumps(to_baseline_json([f])))
    assert load_baseline(str(p)) == [f]


# ------------------------------------------------------------ tier-1 gate

def test_package_is_clean_against_committed_baseline():
    """THE CI gate: linting ps_pytorch_tpu/, tests/, tools/ and
    analysis/ must produce zero findings beyond lint_baseline.json.
    tests/ is included because that is where donated-buffer reuse
    (PSL005) lives — donation is only a warning on the CPU mesh CI runs
    on, so the static check is the only guard; tools/ and analysis/ are
    included because their host loops drive the TPU (PSL002/PSL004
    hazards live there too — tpu_validate.py had 13 live PSL002s before
    this gate covered it)."""
    findings = lint_paths([
        str(REPO / "ps_pytorch_tpu"), str(REPO / "tests"),
        str(REPO / "tools"), str(REPO / "analysis"),
    ])
    baseline = load_baseline(str(REPO / "lint_baseline.json"))
    # paths in the baseline are repo-relative; findings here are absolute
    rel = [
        f.__class__(
            f.rule, str(Path(f.path).resolve().relative_to(REPO)),
            f.line, f.col, f.message, f.text,
        )
        for f in findings
    ]
    new, _, _ = apply_baseline(rel, baseline)
    assert new == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in new
    )


def test_cli_exit_zero_on_package(tmp_path):
    """End-to-end: the exact command CI runs (tools/lint.sh)."""
    proc = subprocess.run(
        [sys.executable, "-m", "ps_pytorch_tpu.lint", "ps_pytorch_tpu",
         "tests", "tools", "analysis",
         "--baseline", "lint_baseline.json"],
        capture_output=True, text=True, cwd=str(REPO),
    )
    assert proc.returncode == 0, proc.stdout + proc.stderr


# ------------------------------------------- PSL006-PSL008 (psdiverge)
#
# The three historical multihost bugs, reproduced verbatim as fixtures.
# Each must trip EXACTLY its intended rule; the blessed
# rank-0-then-broadcast idiom and count-gated single-process tails must
# stay silent.

# PR 3's save_checkpoint: rank 0's write fails and raises BEFORE the
# barrier every other process is already waiting at — ranks 1..N-1 hang
# forever. (The fixed shape holds the error, reaches the collectives,
# and re-raises after; see checkpoint.save_checkpoint.)
PR3_STRANDED_SAVE = """
import jax
from jax.experimental import multihost_utils

def save_checkpoint(path, state, step):
    if jax.process_index() == 0:
        try:
            _write(path, state)
        except OSError as e:
            raise CheckpointWriteError(path) from e
    multihost_utils.sync_global_devices(f"ckpt_save_{step}")
"""

# PR 7's torn-replica resume: every host walks its OWN directory listing
# and restores whatever IT sees newest — a file torn on some replicas of
# a shared dir sends hosts down different fallbacks, and jax never
# cross-checks replicated values.
PR7_TORN_RESUME = """
import jax
import ps_pytorch_tpu.checkpoint as ckpt

def try_resume(target, train_dir):
    pid = jax.process_index()
    steps = ckpt.available_steps(train_dir)
    for step in reversed(steps):
        try:
            return ckpt.load_checkpoint(target, train_dir, step)
        except OSError:
            continue
    return None
"""

# PR 7's per-host agg_count: a wall-clock heuristic adapts the
# aggregation count locally and feeds it straight into the traced step —
# torn counts mean different masked reduces and silently divergent
# replicated params.
PR7_LOCAL_AGG_COUNT = """
import time
import jax
import numpy as np

def train(state, batches, train_step, threshold):
    if jax.process_count() == 1:
        return state
    count = 1
    for batch in batches:
        t0 = time.perf_counter()
        state, metrics = train_step(state, batch, np.int32(count))
        if time.perf_counter() - t0 > threshold:
            count = count + 1
    return state
"""

PSL008_CROSSED_ORDER = """
import os
import jax
from jax.experimental import multihost_utils

def reconcile(path, a, b):
    if os.path.getmtime(path) > 100.0:
        a = multihost_utils.process_allgather(a)
        b = multihost_utils.broadcast_one_to_all(b)
    else:
        b = multihost_utils.broadcast_one_to_all(b)
        a = multihost_utils.process_allgather(a)
    return a, b
"""

# Asymmetric guard: an env-var branch runs the barrier on one path only.
PSL006_ASYMMETRIC_GUARD = """
import os
import jax
from jax.experimental import multihost_utils

def maybe_sync(step):
    if os.environ.get("PS_EAGER_SYNC"):
        multihost_utils.sync_global_devices(f"s_{step}")
"""

# Divergent loop: per-host listing decides how many times each process
# rendezvouses.
PSL006_DIVERGENT_LOOP = """
import os
import jax
from jax.experimental import multihost_utils

def sweep(d, x):
    for name in os.listdir(d):
        x = multihost_utils.process_allgather(x)
    return x
"""


@pytest.mark.parametrize(
    "src,rule",
    [
        (PR3_STRANDED_SAVE, "PSL006"),
        (PR7_TORN_RESUME, "PSL007"),
        (PR7_LOCAL_AGG_COUNT, "PSL007"),
        (PSL008_CROSSED_ORDER, "PSL008"),
        (PSL006_ASYMMETRIC_GUARD, "PSL006"),
        (PSL006_DIVERGENT_LOOP, "PSL006"),
    ],
    ids=["pr3-stranded-save", "pr7-torn-resume", "pr7-local-agg-count",
         "psl008-crossed-order", "asymmetric-guard", "divergent-loop"],
)
def test_divergence_fixture_trips_exactly_its_rule(src, rule):
    findings = _lint(src)
    assert sorted({f.rule for f in findings}) == [rule], [
        (f.rule, f.line, f.message) for f in findings
    ]


# The blessed idiom: process 0 walks per-process state, the choice is
# broadcast, every process acts on the SAME laundered value
# (trainer._try_resume_multihost's shape).
BLESSED_RANK0_BROADCAST = """
import jax
import numpy as np
import ps_pytorch_tpu.checkpoint as ckpt
from jax.experimental import multihost_utils

def resume(target, train_dir):
    chosen = -1
    if jax.process_index() == 0:
        for step in reversed(ckpt.available_steps(train_dir)):
            chosen = step
            break
    chosen = int(multihost_utils.broadcast_one_to_all(np.int32(chosen)))
    if chosen < 0:
        return None
    return ckpt.load_checkpoint(target, train_dir, chosen)
"""

# Barrier-rejoined branches: divergent control with NO collectives inside
# either path, rejoined at a barrier every process reaches.
BLESSED_BARRIER_REJOIN = """
import jax
from jax.experimental import multihost_utils

def log_and_sync(step):
    if jax.process_index() == 0:
        _write_summary(step)
    else:
        _noop(step)
    multihost_utils.sync_global_devices(f"joined_{step}")
"""

# The FIXED PR 3 shape: hold the error, reach every collective, re-raise
# after — raises happen outside divergent control.
BLESSED_HELD_ERROR_SAVE = """
import jax
import numpy as np
from jax.experimental import multihost_utils

def save_checkpoint(path, state, step):
    err = None
    if jax.process_index() == 0:
        try:
            _write(path, state)
        except OSError as e:
            err = e
    ok = int(multihost_utils.broadcast_one_to_all(
        np.int32(0 if err is not None else 1)))
    multihost_utils.sync_global_devices(f"ckpt_save_{step}")
    if not ok:
        raise CheckpointWriteError(path)
"""

# A count-gate early return makes the remainder single-process: per-host
# listings feeding restores are fine when there is only one host.
BLESSED_SINGLE_PROCESS_TAIL = """
import jax
import ps_pytorch_tpu.checkpoint as ckpt

def try_resume(target, train_dir):
    steps = ckpt.available_steps(train_dir)
    if jax.process_count() > 1:
        return _multihost_resume(target, steps)
    for step in reversed(steps):
        return ckpt.load_checkpoint(target, train_dir, step)
    return None

def _multihost_resume(target, steps):
    return None
"""

# Mesh-consensus restore through a module-local helper: the laundered
# choice flows through _restore_step into the real restore calls
# (trainer.py's exact call chain).
BLESSED_RESTORE_HELPER = """
import jax
import numpy as np
import ps_pytorch_tpu.checkpoint as ckpt
from jax.experimental import multihost_utils

def _restore_step(target, train_dir, step):
    raw = ckpt.load_checkpoint_raw(train_dir, step)
    return ckpt.restore_from_raw(target, raw, step)

def resume(target, train_dir):
    chosen = -1
    if jax.process_index() == 0:
        steps = ckpt.available_steps(train_dir)
        if steps:
            chosen = steps[-1]
    chosen = int(multihost_utils.broadcast_one_to_all(np.int32(chosen)))
    if chosen < 0:
        return None
    return _restore_step(target, train_dir, chosen)
"""


@pytest.mark.parametrize(
    "src",
    [
        BLESSED_RANK0_BROADCAST,
        BLESSED_BARRIER_REJOIN,
        BLESSED_HELD_ERROR_SAVE,
        BLESSED_SINGLE_PROCESS_TAIL,
        BLESSED_RESTORE_HELPER,
    ],
    ids=["rank0-broadcast", "barrier-rejoin", "held-error-save",
         "single-process-tail", "restore-helper"],
)
def test_sanctioned_multihost_idiom_is_clean(src):
    assert _lint(src) == []


def test_divergence_skips_modules_without_multihost_markers():
    # same sink shape as PR7_LOCAL_AGG_COUNT, but the module never touches
    # process_index/process_count/multihost_utils: nothing to strand
    src = """
import time
import numpy as np

def train(state, batches, train_step):
    count = 1
    for batch in batches:
        t0 = time.perf_counter()
        state, _ = train_step(state, batch, np.int32(count))
        if time.perf_counter() - t0 > 0.5:
            count = count + 1
    return state
"""
    assert _lint(src) == []


def test_diverge_ok_pragma_suppresses():
    src = PSL006_ASYMMETRIC_GUARD.replace(
        'if os.environ.get("PS_EAGER_SYNC"):',
        'if os.environ.get("PS_EAGER_SYNC"):  # psl: diverge-ok',
    )
    assert _lint(src) == []


def test_rule_scoped_ignore_covers_psl007():
    src = PR7_TORN_RESUME.replace(
        "return ckpt.load_checkpoint(target, train_dir, step)",
        "return ckpt.load_checkpoint(target, train_dir, step)"
        "  # psl: ignore[PSL007]",
    )
    assert _lint(src) == []


def test_baseline_is_empty():
    """The committed baseline carries NO legacy findings: every rule
    (including PSL006-008) gates the repo at zero. A finding that
    belongs in the baseline belongs fixed instead."""
    baseline = json.loads((REPO / "lint_baseline.json").read_text())
    assert baseline["findings"] == []


def test_divergence_gate_is_clean_repo_wide():
    """Tier-1 gate for the psdiverge pass: PSL006-008 over the package,
    tools/, and tests/ produce zero findings — multihost control flow
    stays inside the blessed idiom (or carries a justified pragma)."""
    findings = lint_paths([
        str(REPO / "ps_pytorch_tpu"), str(REPO / "tools"),
        str(REPO / "tests"),
    ])
    diverge = [
        f for f in findings if f.rule in ("PSL006", "PSL007", "PSL008")
    ]
    assert diverge == [], "\n".join(
        f"{f.path}:{f.line}: {f.rule} {f.message}" for f in diverge
    )


def test_consensus_inventory_finds_the_declared_points():
    """PSC110's static half: the walker must see the trainer's consensus
    helpers (a consensus collective whose result is returned), and must
    NOT include functions that never rendezvous."""
    from ps_pytorch_tpu.lint.diverge import consensus_inventory

    inv = consensus_inventory()
    assert "trainer.Trainer._count_consensus" in inv
    assert "trainer.Trainer._stop_consensus" in inv
    assert "trainer.Trainer.train" not in inv
