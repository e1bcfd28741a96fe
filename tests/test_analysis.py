"""Unit tests for the ``repro.analysis`` invariant linter.

Each of the three rule families gets both directions: the rule FIRES on
a minimal seeded violation, and stays SILENT on the repo's sanctioned
pattern for the same situation (call-time env reads, lax.cond-style
decisions, the router's exactly-once future guard, consistent lock
order).  The repo itself must lint clean — that's a test here, not just
a CI step, so a PR that introduces a violation fails tier-1 locally.
"""
import os
import subprocess
import sys
from pathlib import Path

from repro.analysis.core import analyze_paths

REPO = Path(__file__).resolve().parent.parent


def lint(tmp_path, source, name="snippet.py"):
    """Lint one snippet; returns the list of (rule, line) pairs."""
    f = tmp_path / name
    f.write_text(source)
    return [(x.rule, x.line) for x in analyze_paths([f], root=tmp_path)]


def rules(findings):
    return {r for r, _ in findings}


# ---------------------------------------------------------------------------
# family 1: recompile hazards
# ---------------------------------------------------------------------------

def test_env_read_at_import_fires(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "MODE = os.environ.get('REPRO_MODE', 'x')\n"
        "SIZE = int(os.getenv('SIZE', '1'))\n"
        "RAW = os.environ['HOME']\n"
    ))
    assert [r for r, _ in found] == ["env-read-at-import"] * 3
    assert [ln for _, ln in found] == [2, 3, 4]


def test_env_read_sanctioned_patterns_silent(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "def mode():\n"                       # call-time accessor
        "    return os.environ.get('M', 'x')\n"
        "def __getattr__(name):\n"            # PEP 562 lazy attr
        "    return os.environ.get(name, '')\n"
        "os.environ.setdefault('JAX_PLATFORMS', 'cpu')\n"   # write
        "os.environ['XLA_FLAGS'] = ('--foo ' \n"
        "    + os.environ.get('XLA_FLAGS', ''))\n"  # read feeding write
    ))
    assert found == []


def test_env_read_in_class_body_fires(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "class C:\n"
        "    FLAG = os.environ.get('F', '')\n"
    ))
    assert rules(found) == {"env-read-at-import"}


def test_unhashable_static_arg_fires_and_tuple_is_fine(tmp_path):
    src = (
        "import functools, jax\n"
        "@functools.partial(jax.jit, static_argnames=('cfg',))\n"
        "def run(x, cfg=None):\n"
        "    return x\n"
        "def bad():\n"
        "    return run(1, cfg=[1, 2])\n"
        "def good():\n"
        "    return run(1, cfg=(1, 2))\n"
        "wrapped = jax.jit(lambda x, n: x, static_argnums=1)\n"
        "def bad2():\n"
        "    return wrapped(1, {'a': 1})\n"
    )
    found = lint(tmp_path, src)
    assert [r for r, _ in found] == ["unhashable-static-arg"] * 2
    assert [ln for _, ln in found] == [6, 11]


def test_traced_branch_fires_on_if_float_item(tmp_path):
    found = lint(tmp_path, (
        "class Pol:\n"
        "    def decide(self, step, t):\n"
        "        if step > 3:\n"
        "            return 1.0\n"
        "        return float(t)\n"
        "    def update(self, x):\n"
        "        return x.item()\n"
    ))
    assert [r for r, _ in found] == ["traced-branch"] * 3
    assert [ln for _, ln in found] == [3, 5, 7]


def test_traced_branch_sanctioned_patterns_silent(tmp_path):
    # the real policies' shapes: config ifs on self.*, shape/dtype
    # inspection of traced args, jnp.where data-dependence, and
    # dispatch-layer calls (ops.use_pallas()) — all static, all fine
    found = lint(tmp_path, (
        "import jax.numpy as jnp\n"
        "from repro.kernels import ops\n"
        "class Pol:\n"
        "    high_order = 2\n"
        "    def decide(self, crf, acc):\n"
        "        if self._fusable(crf.shape[1:]):\n"
        "            return self.fused(crf)\n"
        "        if ops.use_pallas() and self.high_order > 0:\n"
        "            return 1\n"
        "        return jnp.where(acc > 0.5, 1.0, 0.0)\n"
        "    def _fusable(self, shape):\n"
        "        return len(shape) == 2\n"
        "    def fused(self, crf):\n"
        "        return crf\n"
    ))
    assert found == []


def test_traced_branch_only_scans_hot_methods(tmp_path):
    # helper methods may branch on their args (called outside the scan)
    found = lint(tmp_path, (
        "class Pol:\n"
        "    def resolve(self, n):\n"
        "        if n > 3:\n"
        "            return 1\n"
        "        return 0\n"
    ))
    assert found == []


# ---------------------------------------------------------------------------
# family 2: lock discipline
# ---------------------------------------------------------------------------

_LOCK_CYCLE = (
    "import threading\n"
    "class A:\n"
    "    def __init__(self):\n"
    "        self.l1 = threading.Lock()\n"
    "class B:\n"
    "    def __init__(self, a: A):\n"
    "        self.a = a\n"
    "        self.l2 = threading.Lock()\n"
    "    def fwd(self):\n"
    "        with self.l2:\n"
    "            with self.a.l1:\n"
    "                pass\n"
    "    def rev(self):\n"
    "        with self.a.l1:\n"
    "            with self.l2:\n"
    "                pass\n"
)


def test_lock_order_inversion_fires(tmp_path):
    found = lint(tmp_path, _LOCK_CYCLE)
    assert rules(found) == {"lock-order"}


def test_lock_order_consistent_nesting_silent(tmp_path):
    consistent = _LOCK_CYCLE.replace(
        "    def rev(self):\n"
        "        with self.a.l1:\n"
        "            with self.l2:\n",
        "    def rev(self):\n"
        "        with self.l2:\n"
        "            with self.a.l1:\n")
    assert lint(tmp_path, consistent) == []


def test_lock_order_sees_through_calls(tmp_path):
    # the inversion hides behind a method call: B holds l2 and calls
    # a.take() which acquires l1; A.back() holds l1 and calls b.grab()
    found = lint(tmp_path, (
        "import threading\n"
        "class A:\n"
        "    def __init__(self):\n"
        "        self.l1 = threading.Lock()\n"
        "    def take(self):\n"
        "        with self.l1:\n"
        "            pass\n"
        "class B:\n"
        "    def __init__(self, a: A):\n"
        "        self.a = a\n"
        "        self.l2 = threading.Lock()\n"
        "    def grab(self):\n"
        "        with self.l2:\n"
        "            pass\n"
        "    def fwd(self):\n"
        "        with self.l2:\n"
        "            self.a.take()\n"
        "    def rev(self):\n"
        "        with self.a.l1:\n"
        "            self.grab()\n"
    ))
    assert rules(found) == {"lock-order"}


def test_condition_over_lock_aliases_to_one_node(tmp_path):
    # the FleetRouter shape: _cv wraps _lock, so nesting `with self._cv`
    # around helpers that take `with self._lock` is reentrant, not an
    # inversion (and vice versa)
    found = lint(tmp_path, (
        "import threading\n"
        "class R:\n"
        "    def __init__(self):\n"
        "        self.lk = threading.Lock()\n"
        "        self.cv = threading.Condition(self.lk)\n"
        "    def f(self):\n"
        "        with self.cv:\n"
        "            with self.lk:\n"
        "                pass\n"
    ))
    assert found == []


def test_future_guard_fires_unguarded(tmp_path):
    found = lint(tmp_path, (
        "def resolve(fut, res):\n"
        "    fut.set_result(res)\n"
        "def fail(fut, e):\n"
        "    fut.set_exception(e)\n"
    ))
    assert [r for r, _ in found] == ["future-guard"] * 2


def test_future_guard_sanctioned_patterns_silent(tmp_path):
    # the two repo idioms: try/except InvalidStateError (router) and
    # an `if ... not fut.done()` / set_running_or_notify_cancel guard
    found = lint(tmp_path, (
        "from concurrent.futures import InvalidStateError\n"
        "def resolve(fut, res, counters):\n"
        "    try:\n"
        "        fut.set_result(res)\n"
        "    except InvalidStateError:\n"
        "        counters['duplicate_results'] += 1\n"
        "def fail(fut, e):\n"
        "    if fut is not None and not fut.done():\n"
        "        fut.set_exception(e)\n"
        "def start(fut, res):\n"
        "    if fut.set_running_or_notify_cancel():\n"
        "        fut.set_result(res)\n"
    ))
    assert found == []


# ---------------------------------------------------------------------------
# family 3: donation
# ---------------------------------------------------------------------------

def test_donated_reuse_fires(tmp_path):
    found = lint(tmp_path, (
        "import jax\n"
        "step = jax.jit(lambda x: x + 1, donate_argnums=0)\n"
        "def use(x):\n"
        "    y = step(x)\n"
        "    return x + y\n"     # x's buffer belongs to XLA now
    ))
    assert [r for r, _ in found] == ["donated-reuse"]
    assert found[0][1] == 5


def test_donated_rebind_is_silent(tmp_path):
    found = lint(tmp_path, (
        "import jax\n"
        "step = jax.jit(lambda x: x + 1, donate_argnums=0)\n"
        "def loop(x):\n"
        "    for _ in range(3):\n"
        "        x = step(x)\n"   # rebinding revives the name
        "    return x\n"
    ))
    assert found == []


# ---------------------------------------------------------------------------
# suppressions
# ---------------------------------------------------------------------------

def test_suppression_with_justification_silences(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "# repro: allow[env-read-at-import]: frozen on purpose, "
        "build id\n"
        "BUILD = os.environ.get('BUILD_ID', '')\n"
    ))
    assert found == []


def test_suppression_on_same_line_silences(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "B = os.environ.get('B', '')"
        "  # repro: allow[env-read-at-import]: frozen on purpose\n"
    ))
    assert found == []


def test_bare_suppression_is_itself_flagged(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "# repro: allow[env-read-at-import]\n"
        "BUILD = os.environ.get('BUILD_ID', '')\n"
    ))
    # the allow silences the read but is flagged for missing its why
    assert [r for r, _ in found] == ["bad-suppression"]


def test_unknown_rule_suppression_is_flagged(tmp_path):
    found = lint(tmp_path, (
        "x = 1  # repro: allow[no-such-rule]: whatever\n"
    ))
    assert [r for r, _ in found] == ["bad-suppression"]


def test_suppression_does_not_leak_to_other_rules(tmp_path):
    found = lint(tmp_path, (
        "import os\n"
        "# repro: allow[traced-branch]: wrong rule name for this line\n"
        "BUILD = os.environ.get('BUILD_ID', '')\n"
    ))
    assert rules(found) == {"env-read-at-import"}


def test_parse_error_is_reported_not_raised(tmp_path):
    found = lint(tmp_path, "def broken(:\n")
    assert [r for r, _ in found] == ["parse-error"]


# ---------------------------------------------------------------------------
# the repo itself lints clean (the CI gate, as a tier-1 test)
# ---------------------------------------------------------------------------

def test_repo_lints_clean():
    findings = analyze_paths(
        [REPO / "src", REPO / "tests", REPO / "tools"], root=REPO)
    assert findings == [], "\n".join(str(f) for f in findings)


def test_cli_exit_codes(tmp_path):
    bad = tmp_path / "bad.py"
    bad.write_text("import os\nM = os.environ.get('M', '')\n")
    env_root = dict(os.environ)
    env_root["PYTHONPATH"] = str(REPO / "src") + os.pathsep + \
        env_root.get("PYTHONPATH", "")
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", str(bad)],
        capture_output=True, text=True, env=env_root)
    assert r.returncode == 1
    assert "env-read-at-import" in r.stdout
    r = subprocess.run(
        [sys.executable, "-m", "repro.analysis", "--rules"],
        capture_output=True, text=True, env=env_root)
    assert r.returncode == 0
    assert "lock-order" in r.stdout
