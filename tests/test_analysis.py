"""repro.analysis: AST lint rules (RPL000-RPL006), waiver parsing, the
jaxpr audit self-tests, the committed dispatch budgets, and the int8
k_max guard (the static bound that replaced the silent runtime clamp)."""
import json
from pathlib import Path

import jax.numpy as jnp
import pytest

from repro.analysis import lint_source, lint_tree
from repro.analysis.astlint import iter_rule_ids
from repro.analysis.jaxpr_audit import (DEFAULT_BUDGETS_PATH, _check_budget,
                                        audit_traceable)
from repro.analysis.rules import parse_waivers

REPO = Path(__file__).resolve().parents[1]
SRC = REPO / "src" / "repro"


def _rules(findings):
    return sorted(f.rule for f in findings)


class TestRules:
    """Each fixture trips its rule exactly once."""

    def test_rpl001_item_host_sync(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return x.sum().item()\n")
        fs = lint_source(src, "core/msbfs.py")
        assert _rules(fs) == ["RPL001"]
        assert fs[0].line == 4 and not fs[0].waived

    def test_rpl001_cast_on_traced_value(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return int(x.sum())\n")
        assert _rules(lint_source(src, "core/join.py")) == ["RPL001"]

    def test_rpl001_only_in_jit_reachable_code(self):
        # same sync in a function NOT reachable from any jit root: clean
        src = ("def host_helper(x):\n"
               "    return x.sum().item()\n")
        assert lint_source(src, "core/msbfs.py") == []

    def test_rpl001_not_applied_outside_hot_modules(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def f(x):\n"
               "    return x.sum().item()\n")
        assert lint_source(src, "core/oracle.py") == []

    def test_rpl002_arm_import(self):
        src = "from ..kernels.msbfs_expand.ref import pack_bits\n"
        fs = lint_source(src, "core/engine.py")
        assert _rules(fs) == ["RPL002"]

    def test_rpl002_same_package_registration_allowed(self):
        src = ("from .ref import msbfs_step_ref\n"
               "from .kernel import msbfs_step_pallas\n")
        assert lint_source(src, "kernels/msbfs_expand/ops.py") == []

    def test_rpl003_undeclared_static_shape_arg(self):
        src = ("from functools import partial\n"
               "import jax\n"
               "@partial(jax.jit, static_argnames=('a_col',))\n"
               "def f(x, a_col, out_cap):\n"
               "    return x\n")
        fs = lint_source(src, "core/join.py")
        assert _rules(fs) == ["RPL003"]
        assert "out_cap" in fs[0].message

    def test_rpl004_python_loop_over_device_array(self):
        src = ("import jax\n"
               "import jax.numpy as jnp\n"
               "@jax.jit\n"
               "def f(x):\n"
               "    xs = jnp.arange(4)\n"
               "    t = 0\n"
               "    for v in xs:\n"
               "        t = t + v\n"
               "    return t\n")
        assert _rules(lint_source(src, "core/enumerate.py")) == ["RPL004"]

    def test_rpl005_raw_pow2_shape_math(self):
        src = "def cap_for(k):\n    return 2 ** k\n"
        assert _rules(lint_source(src, "core/cache.py")) == ["RPL005"]

    def test_rpl005_exempt_in_graph_py(self):
        src = "def pow2_ceil(k):\n    return 2 ** k\n"
        assert lint_source(src, "core/graph.py") == []

    def test_rpl006_perf_counter_in_timed_module(self):
        src = ("import time\n"
               "def run_batch(qs):\n"
               "    t0 = time.perf_counter()\n"
               "    return t0\n")
        fs = lint_source(src, "core/engine.py")
        assert _rules(fs) == ["RPL006"]
        assert fs[0].line == 3 and not fs[0].waived

    def test_rpl006_bare_import_form(self):
        src = ("from time import perf_counter\n"
               "def admit(batch):\n"
               "    return perf_counter()\n")
        assert _rules(lint_source(src, "launch/serve.py")) == ["RPL006"]

    def test_rpl006_exempt_in_obs(self):
        # obs/ is the blessed definition site — the span implementation
        # necessarily reads the clock
        src = ("import time\n"
               "def now():\n"
               "    return time.perf_counter()\n")
        assert lint_source(src, "obs/trace.py") == []

    def test_rpl006_not_applied_outside_timed_modules(self):
        # ft/driver.py times external subprocess restarts, not pipeline
        # stages — deliberately off TIMED_MODULE_PATTERNS
        src = ("import time\n"
               "def wait(p):\n"
               "    return time.perf_counter()\n")
        assert lint_source(src, "ft/driver.py") == []
        assert lint_source(src, "launch/dryrun.py") == []

    def test_rpl006_waivable(self):
        src = ("import time\n"
               "def run(qs):\n"
               "    t0 = time.perf_counter()  "
               "# repro-lint: waive[RPL006] clock calibration, not a stage\n"
               "    return t0\n")
        fs = lint_source(src, "core/engine.py")
        assert len(fs) == 1 and fs[0].waived
        assert fs[0].waiver_reason == "clock calibration, not a stage"

    def test_rpl000_malformed_waiver(self):
        src = "x = 1  # repro-lint: waive[RPL999] not a known rule\n"
        assert _rules(lint_source(src, "core/cache.py")) == ["RPL000"]

    def test_rpl000_missing_reason(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return x.sum().item()  # repro-lint: waive[RPL001]\n")
        rules = _rules(lint_source(src, "core/msbfs.py"))
        assert "RPL000" in rules    # empty reason is itself a violation


class TestWaivers:
    def test_waiver_same_line(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return x.sum().item()  "
               "# repro-lint: waive[RPL001] epilogue sync, once per batch\n")
        fs = lint_source(src, "core/msbfs.py")
        assert len(fs) == 1 and fs[0].waived
        assert fs[0].waiver_reason == "epilogue sync, once per batch"

    def test_waiver_own_line_covers_next(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    # repro-lint: waive[RPL001] epilogue sync is intentional\n"
               "    return x.sum().item()\n")
        fs = lint_source(src, "core/msbfs.py")
        assert len(fs) == 1 and fs[0].waived

    def test_waiver_wrong_rule_does_not_apply(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return x.sum().item()  "
               "# repro-lint: waive[RPL004] wrong rule\n")
        fs = lint_source(src, "core/msbfs.py")
        assert len(fs) == 1 and not fs[0].waived

    def test_parse_waivers_ignores_docstrings(self):
        src = ('"""Docs mentioning waive[RPL001] syntax are not waivers."""\n'
               "x = 1\n")
        waivers, malformed = parse_waivers(src)
        assert waivers == {} and malformed == []


class TestRealTree:
    def test_lint_clean(self):
        report = lint_tree(SRC)
        assert report.n_files > 50
        assert report.ok, report.render()

    def test_cli_lint_exit_codes(self, tmp_path):
        from repro.analysis.__main__ import main
        mod = tmp_path / "core"
        mod.mkdir()
        (mod / "msbfs.py").write_text(
            "import jax\n@jax.jit\ndef f(x):\n    return x.sum().item()\n")
        assert main(["--lint", "--root", str(tmp_path)]) == 1
        (mod / "msbfs.py").write_text("def f(x):\n    return x\n")
        assert main(["--lint", "--root", str(tmp_path)]) == 0


class TestJaxprAudit:
    def test_seeded_item_detected(self):
        # the audit's reason for existing: a .item() smuggled into traced
        # code must surface as an audit/trace finding
        fs = audit_traceable(lambda x: x * x.sum().item(),
                             (jnp.ones((4,), jnp.float32),), name="seeded")
        assert [f.rule for f in fs] == ["audit/trace"]

    def test_clean_fn_passes(self):
        fs = audit_traceable(lambda x: x * x.sum(),
                             (jnp.ones((4,), jnp.float32),), name="clean")
        assert fs == []

    def test_budget_regression_detected(self):
        fs = _check_budget("f", "jnp", {"total_eqns": 10}, {"total_eqns": 5})
        assert len(fs) == 1 and "regressed" in fs[0].message
        assert _check_budget("f", "jnp", {"total_eqns": 5},
                             {"total_eqns": 5}) == []

    def test_missing_budget_is_a_finding(self):
        fs = _check_budget("f", "jnp", {"total_eqns": 10}, None)
        assert len(fs) == 1 and fs[0].rule == "audit/budget"

    def test_committed_budgets_exist_and_pin_fused_msbfs(self):
        budgets = json.loads((REPO / DEFAULT_BUDGETS_PATH).read_text())
        # satellite: the fused expand_level budget is committed
        assert "expand_level" in budgets
        # acceptance: the packed MS-BFS sweep stays at ONE kernel dispatch
        # (the msbfs_count distance update) per level on the kernel
        # backend, over the padded and the sliced ELL alike
        for fn in ("msbfs_dist_ell", "msbfs_set_dist_ell",
                   "msbfs_dist_ell_sliced", "msbfs_set_dist_ell_sliced"):
            assert budgets[fn]["interpret"][
                "kernel_dispatches_per_level"] == 1

    @pytest.mark.slow
    def test_full_audit_clean(self):
        from repro.analysis.jaxpr_audit import run_audit
        report = run_audit(REPO / DEFAULT_BUDGETS_PATH)
        assert report.ok, report.render()


class TestKmaxGuard:
    """The int8 distance ceiling is a static precondition, not a clamp."""

    def test_out_of_range_k_max_raises(self):
        from repro.core.msbfs import K_MAX_INT8, msbfs_set_dist_ell
        n = 4
        ell = jnp.full((n + 1, 2), n, jnp.int32)
        seed = jnp.zeros((n + 1,), jnp.int8)
        with pytest.raises(ValueError) as exc:
            msbfs_set_dist_ell(ell, seed, n=n, k_max=K_MAX_INT8 + 1)
        msg = str(exc.value)
        assert f"k_max={K_MAX_INT8 + 1}" in msg
        assert "int8" in msg and "headroom" in msg

    def test_ceiling_leaves_sentinel_headroom(self):
        from repro.core.msbfs import INF_FOR, K_MAX_INT8
        assert INF_FOR(K_MAX_INT8) <= 127 - 6

    def test_in_range_k_max_accepted(self):
        from repro.core.msbfs import msbfs_set_dist_ell
        n = 4
        ell = jnp.full((n + 1, 2), n, jnp.int32)
        seed = jnp.zeros((n + 1,), jnp.int8).at[1].set(1)
        out = msbfs_set_dist_ell(ell, seed, n=n, k_max=3)
        assert out.shape == (n + 1,)

    def test_iter_rule_ids_helper(self):
        src = ("import jax\n"
               "@jax.jit\n"
               "def hot(x):\n"
               "    return x.sum().item()\n")
        assert iter_rule_ids(lint_source(src, "core/msbfs.py")) == {"RPL001"}
