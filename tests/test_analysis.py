"""The hot-path hazard analyzer: AST linter rules, structural invariant
checks, baseline round-trip + reason enforcement, retrace-budget
enforcement, and the ``python -m repro.analysis`` CLI against the real
repo (the same invocation CI blocks on)."""
import dataclasses
import pathlib
import textwrap

import numpy as np
import pytest

from repro.analysis import basefile, hazards, retrace, structure
from repro.analysis.__main__ import main as analysis_main
from repro.analysis.findings import Finding, Suppression, partition

REPO = pathlib.Path(__file__).resolve().parents[1]


def _lint(body: str, **kw):
    src = textwrap.dedent(body)
    return hazards.lint_source(src, "src/repro/fake/mod.py", **kw)


def _rules(findings):
    return sorted({f.rule for f in findings})


# ---------------------------------------------------------------------------
# hazard linter rules
# ---------------------------------------------------------------------------

HEADER = """\
    import jax
    import jax.numpy as jnp
    import numpy as np
    from functools import partial
    from jax import enable_x64
"""


def test_lint_host_np_call_in_traced():
    out = _lint(HEADER + """
    @jax.jit
    def f(x):
        return np.maximum(x, 0)
    """)
    assert "host-np-call" in _rules(out)
    assert out[0].symbol == "f"


def test_lint_scalar_coerce_and_print():
    out = _lint(HEADER + """
    @jax.jit
    def f(x):
        print(x)
        y = float(x)
        return x.item() + y
    """)
    rules = _rules(out)
    assert "host-print" in rules
    assert "host-scalar-coerce" in rules


def test_lint_static_argnames_coercion_is_safe():
    out = _lint(HEADER + """
    @partial(jax.jit, static_argnames=("reg", "n"))
    def f(x, *, reg=0.05, n=4):
        return x * float(reg) + int(n) + len(x) + x.shape[0]
    """)
    assert out == []


def test_lint_loop_and_branch_on_array():
    out = _lint(HEADER + """
    @jax.jit
    def f(xs):
        acc = 0
        for x in xs:
            acc = acc + x
        if (xs > 0).any():
            acc = acc + 1
        return acc

    @jax.jit
    def g(xs):
        for i in range(4):        # static unroll: fine
            xs = xs + i
        return xs
    """)
    rules = _rules(out)
    assert "py-loop-over-array" in rules
    assert "py-branch-on-array" in rules
    assert all(f.symbol == "f" for f in out)


def test_lint_upload_outside_x64():
    out = _lint(HEADER + """
    def host_wrapper(x, entry):
        a = jnp.asarray(x)                  # hazard: ambient dtype
        b = jnp.asarray(x, jnp.float64)     # hazard: f64 needs x64 scope
        c = jnp.asarray(x, jnp.float32)     # fine: intentional narrow
        with enable_x64(True):
            d = jnp.asarray(x)              # fine: lexical x64 scope
        return a, b, c, d
    """)
    assert [f.rule for f in out] == ["jnp-upload-outside-x64"] * 2
    assert {f.line for f in out} == {8, 9}


def test_lint_upload_outside_x64_attribute_scope():
    # a module that only ever calls ``jax.enable_x64(...)`` (no import of
    # the bare name) still owns float64 math and still gets the rule
    out = _lint("""\
    import jax
    import jax.numpy as jnp

    def host_wrapper(x):
        a = jnp.asarray(x)                  # hazard: ambient dtype
        with jax.enable_x64(True):
            b = jnp.asarray(x)              # fine: lexical x64 scope
        return a, b
    """)
    assert [(f.rule, f.line) for f in out] == [("jnp-upload-outside-x64", 5)]


def test_lint_retrace_rules():
    out = _lint(HEADER + """
    @jax.jit
    def entry(x, scale):
        return x * scale

    def wrapper_bad(x, n):
        x = np.pad(x, (0, 8 - n))
        return entry(jnp.asarray(x, jnp.float32), 0.5)

    def wrapper_good(x, n):
        n_pad = bucket(n)
        x = np.pad(x, (0, n_pad - n))
        return entry(jnp.asarray(x, jnp.float32),
                     jnp.asarray(0.5, jnp.float32))
    """)
    rules = [f.rule for f in out]
    assert rules.count("retrace-literal-arg") == 1
    assert rules.count("retrace-unbucketed-pad") == 1
    assert all(f.symbol == "wrapper_bad" for f in out)


def test_lint_pallas_kernel_alias_is_traced():
    out = _lint(HEADER + """
    import functools
    from jax.experimental import pallas as pl

    def _kernel(a_ref, o_ref, *, n_iters):
        o_ref[...] = np.tanh(a_ref[...])    # np in a kernel body: hazard

    @partial(jax.jit, static_argnames=("n_iters",))
    def run(a, *, n_iters=2):
        kernel = functools.partial(_kernel, n_iters=n_iters)
        return pl.pallas_call(kernel, out_shape=None)(a)
    """)
    assert any(f.rule == "host-np-call" and f.symbol == "_kernel"
               for f in out)


def test_lint_extra_traced_registry_hook():
    src = HEADER + """
    def helper(x):
        return np.sum(x)
    """
    assert _lint(src) == []
    out = _lint(src, extra_traced=("helper",))
    assert _rules(out) == ["host-np-call"]


@pytest.mark.parametrize("rel, impl", [
    ("src/repro/core/micro_jax.py", "_scan_assign_multi_impl"),
    ("src/repro/sim/engine_jax.py", "warm_step_impl"),
    ("src/repro/sim/engine_jax.py", "apply_single_impl"),
    ("src/repro/sim/engine_jax.py", "close_step_impl"),
])
def test_lint_named_entry_bodies_are_traced(rel, impl):
    """The production jits wrap named entry functions that call the
    ``*_impl`` bodies; the registry keeps those bodies under the traced
    rules, so a host call planted in one is caught."""
    from repro.analysis import registry
    src = (REPO / rel).read_text()
    at = src.index(f"def {impl}(")
    body = src.index('"""', src.index('"""', at) + 3) + 3
    planted = src[:body] + "\n    _x = np.maximum(1, 2)" + src[body:]
    out = hazards.lint_source(planted, rel,
                              extra_traced=registry.EXTRA_TRACED[rel])
    assert [f.rule for f in out if f.symbol == impl] == ["host-np-call"]


def test_lint_tree_covers_registered_modules():
    files = hazards.jit_extent_files(REPO)
    names = {p.name for p in files}
    assert "micro_jax.py" in names and "engine_jax.py" in names
    assert any(p.match("kernels/*/kernel.py") for p in files)


# ---------------------------------------------------------------------------
# findings / suppression model
# ---------------------------------------------------------------------------


def _finding(rule="r", path="p.py", symbol="s", line=3):
    return Finding(rule=rule, path=path, line=line, symbol=symbol,
                   message="m")


def test_partition_new_suppressed_stale():
    f1, f2 = _finding(symbol="a"), _finding(symbol="b")
    sup_b = Suppression(rule="r", path="p.py", symbol="b", reason="why")
    sup_c = Suppression(rule="r", path="p.py", symbol="c", reason="why")
    new, suppressed, stale = partition([f1, f2], [sup_b, sup_c])
    assert new == [f1]
    assert suppressed == [f2]
    assert stale == [sup_c]


def test_fingerprint_excludes_line():
    assert _finding(line=3).fingerprint == _finding(line=99).fingerprint


def test_baseline_round_trip(tmp_path):
    sups = [Suppression(rule="r1", path="a.py", symbol="f", reason="x"),
            Suppression(rule="r2", path="b.py", symbol="C.m",
                        reason="needs dynamic scope")]
    p = tmp_path / "baseline.toml"
    p.write_text(basefile.dump_suppressions(sups))
    assert basefile.load_suppressions(p) == sups


def test_baseline_requires_reason(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text('[[suppress]]\nrule = "r"\npath = "p"\nsymbol = "s"\n'
                 'reason = ""\n')
    with pytest.raises(basefile.BaselineError, match="reason"):
        basefile.load_suppressions(p)


def test_baseline_rejects_malformed(tmp_path):
    p = tmp_path / "baseline.toml"
    p.write_text("[[suppress]]\nrule = [oops]\n")
    with pytest.raises(basefile.BaselineError):
        basefile.load_suppressions(p)


def test_budget_round_trip_and_validation(tmp_path):
    p = tmp_path / "budget.toml"
    p.write_text(basefile.dump_budget({"micro.retrace.scan_all": 4,
                                       "engine.retrace.warm_step": 1}))
    assert basefile.load_budget(p) == {"micro.retrace.scan_all": 4,
                                       "engine.retrace.warm_step": 1}
    p.write_text('[budget]\n"micro.retrace.scan" = -2\n')
    with pytest.raises(basefile.BaselineError, match="non-negative"):
        basefile.load_budget(p)


# ---------------------------------------------------------------------------
# structural invariants
# ---------------------------------------------------------------------------


def test_structure_clean_on_real_repo():
    """The live registry matches the live dataclasses exactly — any
    drift (new ClusterState/LocalityState field not mirrored or
    documented host_only) fails here before it fails in CI."""
    assert structure.check_pytree_views() == []
    assert structure.check_kernels(REPO) == []
    assert structure.check_registered_dataclasses(REPO) == []


def test_structure_detects_view_drift(monkeypatch):
    from repro.analysis import registry

    view = registry.PYTREE_VIEWS[0]
    # drop a host_only entry: the uncovered source field becomes drift
    trimmed = dataclasses.replace(
        view, host_only={k: v for k, v in view.host_only.items()
                         if k != "power_price"})
    monkeypatch.setattr(registry, "PYTREE_VIEWS", (trimmed,))
    out = structure.check_pytree_views()
    assert [f.rule for f in out] == ["pytree-view-drift"]
    assert "power_price" in out[0].message

    # stale host_only entry: names a field the source no longer has
    bloated = dataclasses.replace(
        view, host_only={**view.host_only, "ghost_field": "gone"})
    monkeypatch.setattr(registry, "PYTREE_VIEWS", (bloated,))
    out = structure.check_pytree_views()
    assert [f.rule for f in out] == ["pytree-view-stale-host-only"]


def test_structure_kernel_missing_ref(tmp_path):
    pkg = tmp_path / "src" / "repro" / "kernels" / "newkern"
    pkg.mkdir(parents=True)
    (pkg / "kernel.py").write_text("x = 1\n")
    (tmp_path / "tests").mkdir()
    out = structure.check_kernels(tmp_path)
    assert _rules(out) == ["kernel-missing-oracle-test",
                           "kernel-missing-ref"]


def test_structure_unregistered_dataclass_field(tmp_path):
    mod = tmp_path / "src" / "mod.py"
    mod.parent.mkdir(parents=True)
    mod.write_text(textwrap.dedent("""\
        import dataclasses, jax
        from functools import partial

        @partial(jax.tree_util.register_dataclass,
                 data_fields=["a"], meta_fields=[])
        @dataclasses.dataclass
        class View:
            a: int
            b: int
    """))
    out = structure.check_registered_dataclasses(tmp_path)
    assert [f.rule for f in out] == ["pytree-unregistered-field"]
    assert "'b'" in out[0].message


# ---------------------------------------------------------------------------
# retrace budget enforcement
# ---------------------------------------------------------------------------


def _counters(shapes):
    from repro.obs.counters import Counters
    c = Counters()
    for name, shape in shapes:
        c.inc(name, shape=shape)
    return c


def test_retrace_observed_shapes_counts_cells():
    c = _counters([("micro.retrace.scan_all", "3x64x9x8"),
                   ("micro.retrace.scan_all", "3x128x9x8"),
                   ("engine.retrace.warm_step", "27"),
                   ("micro.host_sync.scan_all", "x")])   # not a retrace
    obs = retrace.observed_shapes(c)
    assert obs == {"micro.retrace.scan_all": 2,
                   "engine.retrace.warm_step": 1}


def test_retrace_budget_synthetic_extra_bucket():
    """The acceptance scenario: one bucket shape more than the budget
    allows is a hard failure; within budget passes."""
    budget = {"micro.retrace.scan_all": 2}
    ok = _counters([("micro.retrace.scan_all", "3x64x9x8"),
                    ("micro.retrace.scan_all", "3x128x9x8")])
    assert retrace.enforce(ok, budget).ok

    extra = _counters([("micro.retrace.scan_all", "3x64x9x8"),
                       ("micro.retrace.scan_all", "3x128x9x8"),
                       ("micro.retrace.scan_all", "3x256x9x8")])
    report = retrace.check_budget(retrace.observed_shapes(extra), budget)
    assert [f.rule for f in report.violations] == ["retrace-budget-exceeded"]
    with pytest.raises(RuntimeError, match="retrace budget violated"):
        retrace.enforce(extra, budget)


def test_retrace_unbudgeted_counter_fails():
    c = _counters([("engine.retrace.new_kernel", "64")])
    report = retrace.check_budget(retrace.observed_shapes(c), {})
    assert [f.rule for f in report.violations] == [
        "retrace-unbudgeted-counter"]


def test_repo_budget_covers_known_counters():
    budget = basefile.load_budget(REPO / "analysis" / "retrace_budget.toml")
    for name in ("micro.retrace.scan", "micro.retrace.scan_all",
                 "engine.retrace.warm_step", "engine.retrace.apply_single",
                 "engine.retrace.close_step"):
        assert name in budget, name


# ---------------------------------------------------------------------------
# CLI (the CI invocation)
# ---------------------------------------------------------------------------


def test_cli_check_green_on_repo(capsys):
    """`python -m repro.analysis --check` over the real repo: the exact
    blocking CI step must be green."""
    rc = analysis_main(["--root", str(REPO), "--check"])
    out = capsys.readouterr().out
    assert rc == 0, out
    assert "0 new" in out and "0 stale" in out


def test_cli_check_fails_on_unsuppressed(tmp_path, capsys):
    src = tmp_path / "src" / "repro" / "core"
    src.mkdir(parents=True)
    src.joinpath("micro_jax.py").write_text(textwrap.dedent("""\
        import jax
        import numpy as np

        @jax.jit
        def f(x):
            return np.maximum(x, 0)
    """))
    (tmp_path / "src" / "repro" / "kernels").mkdir()
    (tmp_path / "tests").mkdir()
    rc = analysis_main(["--root", str(tmp_path), "--check"])
    assert rc == 1
    assert "host-np-call" in capsys.readouterr().out

    # --write-baseline stamps TODO reasons; --check still fails on them
    rc = analysis_main(["--root", str(tmp_path), "--write-baseline"])
    assert rc == 0
    text = (tmp_path / "analysis" / "baseline.toml").read_text()
    assert "TODO: justify" in text
    rc = analysis_main(["--root", str(tmp_path), "--check"])
    assert rc == 1
    # a human-written reason turns the check green
    (tmp_path / "analysis" / "baseline.toml").write_text(
        text.replace("TODO: justify this suppression", "known legacy"))
    rc = analysis_main(["--root", str(tmp_path), "--check"])
    assert rc == 0


def test_cli_check_fails_on_stale_suppression(tmp_path, capsys):
    (tmp_path / "src" / "repro" / "kernels").mkdir(parents=True)
    (tmp_path / "tests").mkdir()
    (tmp_path / "analysis").mkdir()
    (tmp_path / "analysis" / "baseline.toml").write_text(
        '[[suppress]]\nrule = "host-np-call"\npath = "gone.py"\n'
        'symbol = "f"\nreason = "was real once"\n')
    rc = analysis_main(["--root", str(tmp_path), "--check"])
    assert rc == 1
    assert "stale-suppression" in capsys.readouterr().out
