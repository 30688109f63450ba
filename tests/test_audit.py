"""Static auditor tests: lint rule battery (fixture snippets, no live
tree needed), pragma grammar, the injected-`.item()` lane check, the
jaxpr invariant audit, and the executable-manifest golden regression.

Cost discipline: fixture/pragma/drift tests are pure AST/JSON (ms).  The
jaxpr audit and the manifest SIGNATURE check trace abstract programs
(seconds, nothing compiles, nothing executes).  The full manifest check
(static cost + memory, which needs XLA compiles) runs only under
``REPRO_AUDIT_FULL=1`` — the `make ci-audit` lane; plain pytest still
pins every signature.  Lowering-based tests skip under fake devices
(`make ci-sharded` replays the suite there; the audit lane is defined
device-topology-free).
"""
import json
import os
import re
from pathlib import Path

import pytest

from repro.analysis import lint as lint_mod
from repro.analysis.lint import Finding, lint_source

ROOT = Path(__file__).resolve().parents[1]
SRC = ROOT / "src" / "repro"

FULL = os.environ.get("REPRO_AUDIT_FULL") == "1"
no_fake_devices = pytest.mark.skipif(
    bool(os.environ.get("REPRO_FAKE_DEVICES")),
    reason="audit lane runs without fake devices (single-device lowerings)")


# -- lint rule battery: one known-bad snippet per rule + clean twin -----------

BAD_FIXTURES = [
    # (rule, expected line, snippet)
    ("host-sync", 3, """\
def f(x):
    y = x * 2
    return y.item()
"""),
    ("host-sync", 2, """\
def f(x):
    return float(x)
"""),
    ("host-sync", 3, """\
def f(x):
    import numpy as np
    return np.asarray(x)
"""),
    ("host-sync", 2, """\
def f(x):
    return jax.device_get(x)
"""),
    ("host-sync", 3, """\
def f(x):
    y = g(x)
    return y.block_until_ready()
"""),
    ("traced-branch", 3, """\
def f(x):
    y = jnp.sum(x)
    if y > 0:
        return y
    return -y
"""),
    ("traced-branch", 2, """\
def f(x):
    while jnp.any(x > 0):
        x = x - 1
    return x
"""),
    ("unseeded-rng", 2, """\
def f(n):
    return np.random.normal(0.0, 1.0, n)
"""),
    ("unseeded-rng", 2, """\
def f(n):
    rng = np.random.default_rng()
    return rng.normal(size=n)
"""),
]

CLEAN_FIXTURES = [
    # device-side / statically-safe counterparts: none may fire
    """\
def f(x):
    y = jnp.asarray(x, jnp.float32)
    return jnp.sum(y)
""",
    """\
def f(x):
    scale = float(1.5)
    return x * scale
""",
    """\
def f(x, flag, method):
    if flag and method in ("a", "b"):
        return x
    return -x
""",
    """\
def f(n, seed):
    rng = np.random.default_rng(seed)
    key = jax.random.PRNGKey(0)
    return rng.normal(size=n), key
""",
    """\
def f(x):
    y = jnp.where(x > 0, x, -x)
    return jax.lax.cond(True, lambda v: v, lambda v: -v, y)
""",
]


@pytest.mark.parametrize("rule,line,snippet", BAD_FIXTURES)
def test_lint_flags_bad_fixture(rule, line, snippet):
    findings = lint_source(snippet, "fixture.py", {"f"})
    hits = [(f.rule, f.line) for f in findings]
    assert (rule, line) in hits, (
        f"rule {rule} did not fire at line {line}; findings: {findings}")


@pytest.mark.parametrize("snippet", CLEAN_FIXTURES)
def test_lint_clean_fixture(snippet):
    assert lint_source(snippet, "fixture.py", {"f"}) == []


def test_lint_outside_registered_scope_is_ignored():
    # same bad body, but the def is NOT in the scope registry for the file
    snippet = BAD_FIXTURES[0][2]
    assert lint_source(snippet, "fixture.py", {"other"}) == []


# -- pragma grammar -----------------------------------------------------------

def test_pragma_same_line_suppresses():
    src = """\
def f(x):
    return float(x)  # audit: allow(host-sync) fixture justification
"""
    assert lint_source(src, "fixture.py", {"f"}) == []


def test_pragma_line_above_suppresses():
    src = """\
def f(x):
    # audit: allow(host-sync) fixture justification
    return float(x)
"""
    assert lint_source(src, "fixture.py", {"f"}) == []


def test_pragma_on_def_line_covers_function():
    src = """\
# audit: allow(host-sync) whole-function justification
def f(x):
    y = float(x)
    return int(y)
"""
    assert lint_source(src, "fixture.py", {"f"}) == []


def test_pragma_wrong_rule_id_does_not_suppress():
    src = """\
def f(x):
    return float(x)  # audit: allow(traced-branch) wrong id
"""
    findings = lint_source(src, "fixture.py", {"f"})
    assert [f.rule for f in findings] == ["host-sync"]


def test_bare_pragma_matches_nothing():
    src = """\
def f(x):
    return float(x)  # audit: allow
"""
    assert [f.rule for f in lint_source(src, "fixture.py", {"f"})] \
        == ["host-sync"]


# -- the acceptance check: a deliberately injected .item() fails the lane -----

def test_injected_item_in_traced_scope_fails():
    """Inject a host sync into the episode impl body and assert the lane's
    linter catches it with the real registry spec for core/fleet.py."""
    src = (SRC / "core" / "fleet.py").read_text()
    anchor = re.search(r"\n(    n_local = scene_params\.backgrounds"
                       r"\.shape\[0\][^\n]*)\n", src)
    assert anchor, "fleet._episode_impl anchor line moved; update this test"
    injected = src[:anchor.end(1)] + "\n    _probe = trace.item()" \
        + src[anchor.end(1):]
    findings = lint_source(injected, "core/fleet.py",
                           lint_mod.TRACED_SCOPES["core/fleet.py"])
    inj_line = injected[:injected.index("_probe = trace.item()")].count(
        "\n") + 1
    assert any(f.rule == "host-sync" and f.line == inj_line
               for f in findings), findings


def test_live_tree_lints_clean():
    findings = lint_mod.lint_tree()
    assert findings == [], "\n".join(str(f) for f in findings)


def test_registry_functions_exist():
    """Registry rot guard: every registered traced function still exists
    in its file (renames must update lint.TRACED_SCOPES)."""
    import ast
    for rel, spec in lint_mod.TRACED_SCOPES.items():
        path = SRC / rel
        assert path.exists(), f"registered file missing: {rel}"
        if spec == "*":
            continue
        tree = ast.parse(path.read_text())
        defs = {n.name for n in ast.walk(tree)
                if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))}
        missing = set(spec) - defs
        assert not missing, f"{rel}: registered scopes not found: {missing}"


# -- canonical-config lockstep ------------------------------------------------

def test_canonical_config_matches_harness():
    """The audited programs must fingerprint the executables the scenario
    harness compiles: same pinned DP capacity, same eval_frames, same
    method set."""
    import harness

    from repro.analysis import programs as prog_mod
    assert prog_mod.W_CAP_KBPS == harness.W_CAP_KBPS
    assert prog_mod.EVAL_FRAMES == 3
    assert tuple(prog_mod.METHODS) == tuple(harness.METHODS)


# -- jaxpr invariant audit ----------------------------------------------------

@no_fake_devices
def test_jaxpr_audit_all_invariants_hold():
    from repro.analysis.jaxpr_audit import audit
    failures = audit()
    assert failures == [], "\n".join(failures)


def test_captured_device_constant_is_flagged():
    """A device array closed over by traced code is a constant the first
    (guarded) call would fetch to the host while lowering; a host scalar
    is a literal and is not flagged."""
    import jax
    import jax.numpy as jnp
    import numpy as np
    from repro.analysis.jaxpr_audit import device_constants

    dev = jnp.int32(2 ** 30)
    inner = jax.jit(lambda x: jnp.minimum(x, dev))
    bad = jax.make_jaxpr(lambda x: inner(x) + 1)(jnp.int32(0))
    assert device_constants(bad) == ["int32[]"]
    host = np.int32(2 ** 30)
    good = jax.make_jaxpr(lambda x: jnp.minimum(x, host))(jnp.int32(0))
    assert device_constants(good) == []


# -- executable manifest golden regression ------------------------------------

GOLDEN = ROOT / "tests" / "golden" / "executable_manifest.json"


def _golden():
    assert GOLDEN.exists(), (
        "no committed manifest — regenerate via "
        "`python -m repro.analysis.manifest --write`")
    return json.loads(GOLDEN.read_text())


def test_manifest_covers_the_matrix():
    from repro.analysis.programs import METHODS
    from repro.core.fleet import EPISODE_BUCKETS
    names = list(_golden()["executables"])
    episodes = [n for n in names if n.startswith("episode/")]
    assert len(episodes) == len(METHODS) * len(EPISODE_BUCKETS), episodes
    # the episode matrix plus the host-control loop's slot-step: the
    # episode scan is the only device-control program
    assert sorted(names) == sorted(episodes + ["slot_step/unified"]), names


@no_fake_devices
def test_manifest_signatures_match_golden():
    """Signature/arg/out/donation drift fails even WITHOUT the full lane:
    tracing-only rebuild (no compiles) diffed against the golden — any
    mismatch names the executable and the changed field."""
    from repro.analysis.manifest import build_manifest, diff_manifests
    current = build_manifest(compile_programs=False)
    drift = diff_manifests(_golden(), current)
    assert drift == [], "\n".join(drift)


@no_fake_devices
@pytest.mark.skipif(not FULL, reason="full manifest check (XLA compiles for "
                    "cost/memory) runs in the `make ci-audit` lane")
def test_manifest_full_matches_golden():
    from repro.analysis.manifest import build_manifest, diff_manifests
    drift = diff_manifests(_golden(), build_manifest())
    assert drift == [], "\n".join(drift)


def test_manifest_drift_names_executable_and_field():
    """The drift reporter's contract: failures name the program + field."""
    from repro.analysis.manifest import diff_manifests
    golden = _golden()
    current = json.loads(json.dumps(golden))     # deep copy
    entry = current["executables"]["episode/deepstream/b8"]
    entry["signature"] = "0" * 16
    entry["cost"]["flops"] = entry["cost"]["flops"] + 1.0
    drift = diff_manifests(golden, current)
    joined = "\n".join(drift)
    assert "episode/deepstream/b8" in joined
    assert "'signature'" in joined and "'cost'" in joined
    # untouched programs stay silent
    assert "episode/jcab/b8" not in joined
