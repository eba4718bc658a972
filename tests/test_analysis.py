"""Rule-engine coverage: a bad/good fixture pair per rule (each bad
fixture is the test that would fail if its rule were dropped), pragma
suppression semantics, and the self-lint pin that keeps the repo clean."""
import os
import textwrap

import pytest

from repro.analysis import AnalysisConfig, RULES, run_paths

REPO_ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


def lint(tmp_path, sources, config=None, only=None):
    for rel, text in sources.items():
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(textwrap.dedent(text))
    return run_paths([str(tmp_path)], repo_root=str(tmp_path),
                     config=config, only=only)


def rules_hit(findings):
    return {f.rule for f in findings}


def test_rules_registered():
    assert len(RULES) >= 8
    assert set(RULES) >= {"jit-outside-cache", "host-sync", "nondeterminism",
                          "tracer-hazard", "unhashable-static",
                          "kernel-parity", "donation-miss",
                          "exception-swallow"}


# -- jit-outside-cache -------------------------------------------------------

def test_jit_outside_cache_bad_and_good(tmp_path):
    bad = lint(tmp_path, {"a.py": """
        import jax
        def make(model):
            return jax.jit(model.loss)
        """}, only=["jit-outside-cache"])
    assert [f.rule for f in bad] == ["jit-outside-cache"]
    assert bad[0].line == 4

    good = lint(tmp_path, {"b.py": """
        import jax
        def loss(p, b):
            return p
        loss_jit = jax.jit(loss)          # module scope: compiled once
        """}, only=["jit-outside-cache"])
    assert not [f for f in good if f.path == "b.py"]


def test_jit_sanctioned_module_allowed(tmp_path):
    cfg = AnalysisConfig(jit_sanctioned=("engine/",))
    out = lint(tmp_path, {"engine/suite.py": """
        import jax
        def build(fn):
            return jax.jit(fn)
        """}, config=cfg, only=["jit-outside-cache"])
    assert not out


# -- host-sync ---------------------------------------------------------------

HOT_CFG = AnalysisConfig(hot_entry_points=("main",),
                         host_stage_boundary=frozenset({"sample_round"}))


def test_host_sync_reachable_bad(tmp_path):
    bad = lint(tmp_path, {"hot.py": """
        import numpy as np
        def main(xs):
            for x in xs:
                record(x)
        def record(x):
            return float(x.mean()), np.asarray(x)
        """}, config=HOT_CFG, only=["host-sync"])
    assert rules_hit(bad) == {"host-sync"}
    assert len(bad) >= 2          # float(...) and np.asarray(...)


def test_host_sync_stops_at_stage_boundary(tmp_path):
    out = lint(tmp_path, {"hot.py": """
        import numpy as np
        def main(xs):
            sample_round(xs)
        def sample_round(xs):
            return np.asarray(xs)      # host stage: sanctioned by design
        def unrelated(x):
            return float(x)            # not reachable from main
        """}, config=HOT_CFG, only=["host-sync"])
    assert not out


# -- nondeterminism ----------------------------------------------------------

NONDET_CFG = AnalysisConfig(nondet_scope=("",))


def test_nondeterminism_bad_sources(tmp_path):
    bad = lint(tmp_path, {"sel.py": """
        import random, time
        import numpy as np
        def pick(xs):
            t = time.time()
            i = random.randrange(len(xs))
            return xs[i] + np.random.rand(), t
        """}, config=NONDET_CFG, only=["nondeterminism"])
    assert rules_hit(bad) == {"nondeterminism"}
    assert len(bad) == 3          # time.time, random.randrange, np.random.rand


def test_nondeterminism_seeded_streams_allowed(tmp_path):
    out = lint(tmp_path, {"sel.py": """
        import numpy as np
        def pick(xs, seed):
            rng = np.random.RandomState(seed)
            return xs[rng.randint(len(xs))]
        """}, config=NONDET_CFG, only=["nondeterminism"])
    assert not out
    bad = lint(tmp_path, {"sel2.py": """
        import numpy as np
        def pick(xs):
            return xs[np.random.default_rng().integers(len(xs))]
        """}, config=NONDET_CFG, only=["nondeterminism"])
    assert [f.rule for f in bad] == ["nondeterminism"]   # ctor unseeded


# -- tracer-hazard -----------------------------------------------------------

def test_tracer_hazard_bad_and_good(tmp_path):
    bad = lint(tmp_path, {"t.py": """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(x):
            if jnp.sum(x) > 0:
                return x
            return -x
        """}, only=["tracer-hazard"])
    assert [f.rule for f in bad] == ["tracer-hazard"]

    good = lint(tmp_path, {"g.py": """
        import jax
        import jax.numpy as jnp
        @jax.jit
        def step(x):
            return jnp.where(jnp.sum(x) > 0, x, -x)
        def host_side(x):
            if jnp.sum(x) > 0:        # not a jitted function: fine
                return x
        """}, only=["tracer-hazard"])
    assert not [f for f in good if f.path == "g.py"]


def test_tracer_hazard_catches_suite_registration(tmp_path):
    """Functions registered via jax.jit(self._impl, ...) — the jit-suite
    pattern — are treated as jitted even without a decorator."""
    bad = lint(tmp_path, {"s.py": """
        import jax
        import jax.numpy as jnp
        class C:
            def _impl(self, x):
                while jnp.any(x > 0):
                    x = x - 1
                return x
            def __init__(self):
                self._f = jax.jit(self._impl)
        """}, only=["tracer-hazard"])
    assert [f.rule for f in bad] == ["tracer-hazard"]


# -- unhashable-static -------------------------------------------------------

def test_unhashable_static_bad_and_good(tmp_path):
    bad = lint(tmp_path, {"u.py": """
        import jax
        def f(x, history=[]):
            return x
        g = jax.jit(f, static_argnums=[1])
        """}, only=["unhashable-static"])
    assert [f.rule for f in bad] == ["unhashable-static"] * 2

    good = lint(tmp_path, {"v.py": """
        import jax
        def f(x, history=None):
            return x
        g = jax.jit(f, static_argnums=(1,))
        """}, only=["unhashable-static"])
    assert not [f for f in good if f.path == "v.py"]


# -- kernel-parity -----------------------------------------------------------

KERNEL_GOOD = {
    "kernels/foo.py": """
        from jax.experimental import pallas as pl
        def foo(x):
            return pl.pallas_call(None)(x)
        def foo_jnp(x):
            return x
        """,
    "kernels/ops.py": "# dispatches foo by platform\n",
    "tests/test_kernels.py": "# exercises foo and foo_jnp parity\n",
}


def kernel_cfg():
    return AnalysisConfig(kernel_dir="kernels/",
                          kernel_exclude=("ops.py",),
                          kernel_tests="tests/test_kernels.py",
                          kernel_dispatch="kernels/ops.py")


def test_kernel_parity_good(tmp_path):
    out = lint(tmp_path, KERNEL_GOOD, config=kernel_cfg(),
               only=["kernel-parity"])
    assert not out


def test_kernel_parity_flags_missing_fallback_dispatch_and_test(tmp_path):
    srcs = dict(KERNEL_GOOD)
    srcs["kernels/foo.py"] = """
        from jax.experimental import pallas as pl
        def foo(x):
            return pl.pallas_call(None)(x)
        """
    srcs["kernels/ops.py"] = "# nothing here\n"
    srcs["tests/test_kernels.py"] = "# nothing here\n"
    bad = lint(tmp_path, srcs, config=kernel_cfg(), only=["kernel-parity"])
    msgs = " ".join(f.message for f in bad)
    assert rules_hit(bad) == {"kernel-parity"} and len(bad) == 3
    assert "fallback" in msgs and "dispatch" in msgs and "parity" in msgs


def test_kernel_parity_flags_untested_fallback(tmp_path):
    srcs = dict(KERNEL_GOOD)
    srcs["tests/test_kernels.py"] = "# mentions foo but not the fallback\n"
    bad = lint(tmp_path, srcs, config=kernel_cfg(), only=["kernel-parity"])
    assert [f.rule for f in bad] == ["kernel-parity"]
    assert "foo_jnp" in bad[0].message


# -- donation-miss -----------------------------------------------------------

DON_CFG = AnalysisConfig(donation_scope=("serve/",),
                         donation_tree_params=("params", "stacked"))


def test_donation_miss_bad_and_good(tmp_path):
    bad = lint(tmp_path, {"serve/e.py": """
        import jax
        def step(params, x):
            return params
        f = jax.jit(step)
        """}, config=DON_CFG, only=["donation-miss"])
    assert [f.rule for f in bad] == ["donation-miss"]
    assert "params" in bad[0].message and "donate_argnums" in bad[0].message

    good = lint(tmp_path, {"serve/g.py": """
        import jax
        def write(stacked, p, b):
            return stacked
        f = jax.jit(write, donate_argnums=0)       # donates: fine
        def sample(tokens, key):
            return tokens
        g = jax.jit(sample)                        # no params-sized tree
        """}, config=DON_CFG, only=["donation-miss"])
    assert not [f for f in good if f.path == "serve/g.py"]


def test_donation_miss_lambda_target(tmp_path):
    bad = lint(tmp_path, {"serve/l.py": """
        import jax
        f = jax.jit(lambda stacked, b: stacked)
        """}, config=DON_CFG, only=["donation-miss"])
    assert [f.rule for f in bad] == ["donation-miss"]


def test_donation_miss_outside_scope_ignored(tmp_path):
    out = lint(tmp_path, {"probe/e.py": """
        import jax
        def step(params, x):
            return params
        f = jax.jit(step)
        """}, config=DON_CFG, only=["donation-miss"])
    assert not out


def test_donation_miss_pragma_escape(tmp_path):
    out = lint(tmp_path, {"serve/p.py": """
        import jax
        def step(params, x):
            return params
        f = jax.jit(step)  # repro: allow[donation-miss] -- params shared across slots
        """}, config=DON_CFG, only=["donation-miss"])
    assert not out


# -- exception-swallow -------------------------------------------------------

SWALLOW_CFG = AnalysisConfig(swallow_scope=("core/",))


def test_exception_swallow_bad(tmp_path):
    out = lint(tmp_path, {"core/a.py": """
        def load(path):
            try:
                return open(path).read()
            except:
                return None

        def tick(items):
            for x in items:
                try:
                    x.step()
                except Exception:
                    pass
        """}, config=SWALLOW_CFG, only=["exception-swallow"])
    assert [f.rule for f in out] == ["exception-swallow"] * 2
    assert {f.line for f in out} == {5, 12}


def test_exception_swallow_good(tmp_path):
    out = lint(tmp_path, {"core/b.py": """
        import shutil

        def save(tmp):
            try:
                return write(tmp)
            except Exception:
                shutil.rmtree(tmp, ignore_errors=True)
                raise

        def verify(path):
            try:
                return parse(path), "ok"
            except (OSError, ValueError) as e:
                return None, str(e)

        def load(z):
            try:
                return z.read()
            except Exception as e:
                return None  # repro: allow[exception-swallow] -- verdict returned to caller
        """}, config=SWALLOW_CFG, only=["exception-swallow"])
    assert not out


def test_exception_swallow_outside_scope_ignored(tmp_path):
    out = lint(tmp_path, {"tools/c.py": """
        def f():
            try:
                g()
            except Exception:
                pass
        """}, config=SWALLOW_CFG, only=["exception-swallow"])
    assert not out


# -- pragmas -----------------------------------------------------------------

def test_pragma_suppresses_with_reason(tmp_path):
    out = lint(tmp_path, {"p.py": """
        import jax
        def make(fn):
            return jax.jit(fn)  # repro: allow[jit-outside-cache] -- test fixture
        """}, only=["jit-outside-cache"])
    assert not out


def test_pragma_line_above(tmp_path):
    out = lint(tmp_path, {"p.py": """
        import jax
        def make(fn):
            # repro: allow[jit-outside-cache] -- test fixture
            return jax.jit(fn)
        """}, only=["jit-outside-cache"])
    assert not out


def test_pragma_without_reason_rejected(tmp_path):
    out = lint(tmp_path, {"p.py": """
        import jax
        def make(fn):
            return jax.jit(fn)  # repro: allow[jit-outside-cache]
        """}, only=["jit-outside-cache"])
    # reasonless pragma does NOT suppress, and is itself a finding
    assert rules_hit(out) == {"jit-outside-cache", "pragma"}


def test_pragma_unknown_rule_rejected(tmp_path):
    out = lint(tmp_path, {"p.py": """
        x = 1  # repro: allow[no-such-rule] -- because
        """})
    assert rules_hit(out) == {"pragma"}
    assert "no-such-rule" in out[0].message


def test_pragma_only_suppresses_named_rule(tmp_path):
    out = lint(tmp_path, {"p.py": """
        import jax
        def make(fn, xs=[]):
            return jax.jit(fn)  # repro: allow[unhashable-static] -- wrong rule named
        """}, only=["jit-outside-cache", "unhashable-static"])
    assert rules_hit(out) == {"jit-outside-cache", "unhashable-static"}


# -- CLI + self-lint ---------------------------------------------------------

def test_cli_exit_codes(tmp_path):
    from repro.analysis.__main__ import main
    (tmp_path / "bad.py").write_text(
        "import jax\ndef f(g):\n    return jax.jit(g)\n")
    assert main([str(tmp_path / "bad.py"), "--root", str(tmp_path)]) == 1
    assert main(["--list-rules"]) == 0


def test_cli_json_findings(tmp_path, capsys):
    """--json: the machine-readable findings the CI lint job turns into
    per-line GitHub annotations."""
    import json

    from repro.analysis.__main__ import main
    (tmp_path / "bad.py").write_text(
        "import jax\ndef f(g):\n    return jax.jit(g)\n")

    rc = main(["--json", str(tmp_path / "bad.py"), "--root", str(tmp_path)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 1 and not report["ok"]
    assert [(f["rule"], f["line"]) for f in report["findings"]] == [
        ("jit-outside-cache", 3)]
    assert report["findings"][0]["path"] == "bad.py"

    (tmp_path / "ok.py").write_text("x = 1\n")
    rc = main(["--json", str(tmp_path / "ok.py"), "--root", str(tmp_path)])
    report = json.loads(capsys.readouterr().out)
    assert rc == 0 and report["ok"] and report["findings"] == []


def test_self_lint_repo_clean():
    """The acceptance pin: the linted tree (src benchmarks examples) is
    clean under every rule — new violations need a fix or a reasoned
    pragma to land."""
    findings = run_paths(["src", "benchmarks", "examples"],
                         repo_root=REPO_ROOT)
    assert not findings, "\n".join(f.format() for f in findings)
