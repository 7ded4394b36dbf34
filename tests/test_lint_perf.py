"""graftlint Layer P fixtures: the three seeded acceptance bugs from
ISSUE 13 — a weak-type scalar retrace treadmill, a bf16→f32 upcast
inside the bf16 scoring scope, and unscoped-FLOP growth — plus scoped
cost attribution, the hard scoring-fraction ceiling (never demoted),
the HLO fusion/precision scan on crafted text, retrace churn naming,
the GL130–GL133 rule fixtures, and the all-or-nothing multi-golden
commit behind the atomic ``--regen``. Toy programs keep tier-1
compiles tiny; the full plan matrix is slow-tier."""

import json
import textwrap

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from mercury_tpu.lint import golden, lint_source, perf, tracecheck


def ids(src, **kw):
    return [f.rule_id for f in lint_source(textwrap.dedent(src), **kw)]


def toy_perf_step():
    """Tiny step with a scoring-scope matmul, a grad-sync reduction, and
    a deliberately unscoped matmul (the compute nobody claimed)."""
    def step(x, w, v):
        with jax.named_scope("mercury_scoring"):
            s = x @ w
        with jax.named_scope("mercury_grad_sync"):
            g = jnp.sum(s)
        y = x @ v  # unscoped on purpose
        return g + jnp.sum(y)
    return step


def toy_perf_args(score_dim=4):
    return (jnp.ones((8, 16)), jnp.ones((16, score_dim)),
            jnp.ones((16, 64)))


def toy_perf_budgets(measurement):
    """A perf budgets document recorded from ``measurement`` under the
    running jax version (so comparisons run in hard-error mode)."""
    return {
        "schema": perf.SCHEMA,
        "provenance": {"jax": jax.__version__,
                       "flop_tolerance": perf.DEFAULT_TOLERANCE},
        "plans": {measurement.plan: measurement.as_budget()},
        "retrace": {},
    }


class TestCostAttribution:
    def test_scopes_and_unscoped_measured(self):
        m = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(), "toy", {})
        assert m.scope_flops["mercury_scoring"] > 0
        assert m.scope_flops["mercury_grad_sync"] > 0
        assert m.unscoped_flops > 0
        assert 0 < m.scoring_flop_frac < 1
        assert m.est_total_flops >= sum(m.scope_flops.values())
        assert m.scope_intensity()["mercury_scoring"] > 0

    def test_self_comparison_clean(self):
        m = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(), "toy", {})
        errors, warnings = perf.compare_perf_budgets(
            [m], toy_perf_budgets(m))
        assert errors == [], "\n".join(errors)
        assert warnings == []

    def test_missing_plan_budget_is_an_error(self):
        m = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(), "toy", {})
        doc = toy_perf_budgets(m)
        doc["plans"] = {}
        errors, _ = perf.compare_perf_budgets([m], doc)
        assert any("no committed perf budget" in e for e in errors)

    def test_wrong_schema_rejected(self, tmp_path):
        p = tmp_path / "perf_budgets.json"
        p.write_text(json.dumps({"schema": "something_else",
                                 "plans": {}}))
        with pytest.raises(ValueError, match="schema"):
            perf.load_perf_budgets(str(p))

    def test_scan_trip_count_weights_flops(self):
        def looped(x):
            def body(c, _):
                return c @ x, None
            out, _ = jax.lax.scan(body, jnp.ones((16, 16)), None,
                                  length=8)
            return jnp.sum(out)

        def once(x):
            return jnp.sum(jnp.ones((16, 16)) @ x)

        args = (jnp.ones((16, 16)),)
        flops_loop = sum(
            perf.eqn_flops(e) * m for e, m in perf.walk_costed_eqns(
                jax.make_jaxpr(looped)(*args)))
        flops_once = sum(
            perf.eqn_flops(e) * m for e, m in perf.walk_costed_eqns(
                jax.make_jaxpr(once)(*args)))
        assert flops_loop > 5 * flops_once


class TestScoringCeiling:
    """Acceptance fixture: the hard scoring-FLOPs-fraction ceiling and
    the unscoped-FLOP-growth finding (seeded bug: sampler work grows)."""

    def test_ceiling_breach_is_hard_error(self):
        good = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(score_dim=4), "toy", {})
        bloated = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(score_dim=96), "toy", {})
        assert bloated.scoring_flop_frac > good.scoring_flop_frac
        errors, _ = perf.compare_perf_budgets(
            [bloated], toy_perf_budgets(good))
        diff = "\n".join(errors)
        assert "above the committed ceiling" in diff
        assert "scoring-cost economics" in diff

    def test_ceiling_never_demoted_cross_version(self):
        good = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(score_dim=4), "toy", {})
        bloated = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(score_dim=96), "toy", {})
        doc = toy_perf_budgets(good)
        doc["provenance"]["jax"] = "0.0.0-not-this"
        errors, warnings = perf.compare_perf_budgets([bloated], doc)
        assert any("above the committed ceiling" in e for e in errors)
        # ... while the ratcheted count diffs DID demote
        assert any("recorded under jax" in w for w in warnings)
        assert not any("cost profile deviates" in e for e in errors)

    def test_unscoped_flop_growth_flagged(self):
        good = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(), "toy", {})
        grown = perf.PerfMeasurement(plan="toy", config={})
        grown.cost_flops = good.cost_flops
        grown.cost_bytes = good.cost_bytes
        grown.scope_flops = dict(good.scope_flops)
        grown.scope_bytes = dict(good.scope_bytes)
        grown.est_total_flops = good.est_total_flops + good.unscoped_flops
        grown.unscoped_flops = good.unscoped_flops * 2
        grown.scoring_flop_frac = good.scoring_flop_frac
        grown.scope_layout_ops = {
            k: dict(v) for k, v in good.scope_layout_ops.items()}
        grown.unfused_elementwise = good.unfused_elementwise
        errors, _ = perf.compare_perf_budgets(
            [grown], toy_perf_budgets(good))
        diff = "\n".join(errors)
        assert "unscoped FLOP growth" in diff
        assert "compute outside every mercury scope" in diff


_CRAFTED_HLO = """\
ENTRY %main (p0: bf16[4,4]) -> f32[4,4] {
  %x = bf16[4,4]{1,0} parameter(0)
  %up = f32[4,4]{1,0} convert(bf16[4,4]{1,0} %x), metadata={op_name="jit(step)/mercury_scoring/convert_element_type"}
  %norm = f32[4,4]{1,0} convert(u8[4,4]{1,0} %pix), metadata={op_name="jit(step)/mercury_scoring/convert_element_type"}
  %t = f32[4,4]{1,0} transpose(f32[4,4]{1,0} %up), metadata={op_name="jit(step)/mercury_scoring/transpose"}
  %c = f32[4,4]{1,0} copy(f32[4,4]{1,0} %t), metadata={op_name="jit(step)/mercury_grad_sync/copy"}
  %escaped = f32[4,4]{1,0} multiply(f32[4,4]{1,0} %c, f32[4,4]{1,0} %c), metadata={op_name="jit(step)/mercury_augmentation/mercury_input_fuse/mul"}
  ROOT %y = f32[4,4]{1,0} add(f32[4,4]{1,0} %escaped, f32[4,4]{1,0} %c)
}
%fused_computation.1 (param0: f32[4,4]) -> f32[4,4] {
  %p = f32[4,4]{1,0} parameter(0)
  ROOT %m = f32[4,4]{1,0} multiply(f32[4,4]{1,0} %p, f32[4,4]{1,0} %p), metadata={op_name="jit(step)/mercury_augmentation/mercury_input_fuse/mul"}
}
"""


class TestHloScan:
    """scan_hlo on crafted HLO text — the unit contract, independent of
    what this jax build's CPU pipeline happens to emit."""

    def test_bf16_upcast_flagged_input_normalization_not(self):
        scan = perf.scan_hlo(_CRAFTED_HLO, "toy")
        # exactly the bf16-operand convert; the u8→f32 input
        # normalization (%norm) is the designed dataflow
        assert len(scan["f32_scoring_converts"]) == 1
        msg = scan["f32_scoring_converts"][0]
        assert "bf16→f32 upcast" in msg
        assert "mercury_scoring" in msg

    def test_layout_churn_counted_per_scope(self):
        scan = perf.scan_hlo(_CRAFTED_HLO, "toy")
        assert scan["scope_layout_ops"] == {
            "mercury_scoring": {"transpose": 1},
            "mercury_grad_sync": {"copy": 1},
        }

    def test_unfused_elementwise_counted_outside_fusions_only(self):
        scan = perf.scan_hlo(_CRAFTED_HLO, "toy")
        # %escaped counts; the same op inside %fused_computation.1 does
        # not — it is where the compiler put it deliberately
        assert scan["unfused_elementwise"] == 1
        assert any("escaped fusion" in e
                   for e in scan["unfused_examples"])

    def test_unattributed_ops_ignored(self):
        scan = perf.scan_hlo(
            "ENTRY %main (p0: f32[4]) -> f32[4] {\n"
            "  ROOT %c = f32[4]{0} convert(bf16[4]{0} %x)\n"
            "}\n", "toy")
        assert scan["f32_scoring_converts"] == []


class TestBf16UpcastLeak:
    """Acceptance fixture: a bf16 scoring input explicitly upcast to f32
    inside mercury_scoring on a ``scoring_dtype=bfloat16`` plan — the
    compiled-HLO scan must name it, and the invariant must hold as a
    hard error."""

    def leaky_args(self):
        return (jnp.ones((8, 16), jnp.bfloat16), jnp.ones((16, 4)))

    def test_upcast_detected_end_to_end(self):
        def leaky(xb, w):
            with jax.named_scope("mercury_scoring"):
                y = xb.astype(jnp.float32) @ w  # the seeded fallback
                return jnp.sum(y)

        m = perf.measure_perf_step(
            leaky, self.leaky_args(), "toy_bf16",
            {"scoring_dtype": "bfloat16"})
        assert m.f32_scoring_converts, "upcast not detected"
        errors = perf.check_perf_invariants(m)
        assert any("bf16→f32 upcast" in e for e in errors)

    def test_leak_is_always_an_error_even_cross_version(self):
        def leaky(xb, w):
            with jax.named_scope("mercury_scoring"):
                return jnp.sum(xb.astype(jnp.float32) @ w)

        good = perf.measure_perf_step(
            toy_perf_step(), toy_perf_args(), "toy_bf16", {})
        bad = perf.measure_perf_step(
            leaky, self.leaky_args(), "toy_bf16",
            {"scoring_dtype": "bfloat16"})
        doc = toy_perf_budgets(good)
        doc["provenance"]["jax"] = "0.0.0-not-this"
        errors, _ = perf.compare_perf_budgets([bad], doc)
        assert any("bf16→f32 upcast" in e for e in errors)

    def test_clean_bf16_scoring_has_no_findings(self):
        def clean(xb, w):
            with jax.named_scope("mercury_scoring"):
                y = xb @ w.astype(jnp.bfloat16)
            return jnp.sum(y.astype(jnp.float32))  # upcast OUTSIDE

        m = perf.measure_perf_step(
            clean, self.leaky_args(), "toy_bf16",
            {"scoring_dtype": "bfloat16"})
        assert m.f32_scoring_converts == []
        assert perf.check_perf_invariants(m) == []

    def test_invariant_gated_on_bf16_config(self):
        m = perf.PerfMeasurement(plan="toy", config={})
        m.f32_scoring_converts = ["plan toy: bf16→f32 upcast ..."]
        assert perf.check_perf_invariants(m) == []


class TestRetraceGuard:
    """Acceptance fixture: the weak-type scalar retrace treadmill —
    caught live by the CompileMonitor, diagnosed by the churn diff."""

    def test_weak_type_flip_compiles_in_steady_state(self):
        inner = jax.jit(lambda s, lr: s * lr)
        calls = {"n": 0}

        def step(s):
            calls["n"] += 1
            # the seeded bug: after warmup the learning rate arrives as
            # a strongly-typed np.float32 instead of the weak python
            # float — a different jit cache key, a fresh compile
            lr = (0.1 if calls["n"] <= tracecheck.WARMUP_CALLS
                  else np.float32(0.1))
            return inner(s, lr)

        m = tracecheck.measure_step_retraces(
            step, (jnp.ones((4,)),), "toy", {}, steps=4)
        assert m.steady_compiles >= 1
        assert m.churn, "churn diagnosis missing"
        # the flip hides in a closure, and the diagnosis says so
        assert any("closure/global state" in line for line in m.churn)

    def test_stable_step_steady_state_clean(self):
        step = jax.jit(lambda s: s * 2.0)
        m = tracecheck.measure_step_retraces(
            step, (jnp.ones((4,)),), "toy", {}, steps=4)
        assert m.steady_compiles == 0
        assert m.steady_traces == 0
        assert m.churn == []

    def test_monitor_counts_a_fresh_compile(self):
        mon = tracecheck.CompileMonitor()
        f = jax.jit(lambda x: x + 1.0)
        with mon:
            f(jnp.ones((3,)))
        traces, compiles = mon.snapshot()
        assert compiles >= 1
        assert traces >= 1
        # every compile is accounted for: its seconds, and whether the
        # persistent cache (on in this suite) had it
        assert mon.compile_secs > 0.0
        assert mon.cache_hits + mon.cache_misses >= 1

    def test_describe_churn_names_weak_type_leaf(self):
        sig_weak = tracecheck.signature_of((jnp.ones((4,)), 0.1))
        sig_strong = tracecheck.signature_of(
            (jnp.ones((4,)), np.float32(0.1)))
        lines = tracecheck.describe_churn(sig_weak, sig_strong)
        assert len(lines) == 1
        assert "weak" in lines[0]
        assert "float32" in lines[0]

    def test_describe_churn_empty_for_identical_signatures(self):
        sig = tracecheck.signature_of((jnp.ones((4,)), 0.1))
        assert tracecheck.describe_churn(sig, dict(sig)) == []


def _retrace_expectation(**kw):
    doc = {"steps": 4, "warmup_calls": tracecheck.WARMUP_CALLS,
           "warmup_traces": 2, "warmup_compiles": 2,
           "steady_traces": 0, "steady_compiles": 0,
           "backend": "events"}
    doc.update(kw)
    return doc


class TestRetraceComparison:
    def test_steady_compile_is_hard_error_with_churn(self):
        m = tracecheck.RetraceMeasurement(
            plan="toy", steps=4, warmup_traces=2, warmup_compiles=2,
            steady_traces=1, steady_compiles=1,
            churn=["plan toy call 3: arg[1]: float[] weak -> "
                   "float32[]"])
        errors, _ = tracecheck.compare_retraces(
            [m], {"retrace": {"toy": _retrace_expectation()}})
        diff = "\n".join(errors)
        assert "compile-per-step treadmill" in diff
        assert "float32" in diff

    def test_warmup_variance_is_warn_only(self):
        m = tracecheck.RetraceMeasurement(
            plan="toy", steps=4, warmup_traces=9, warmup_compiles=3)
        errors, warnings = tracecheck.compare_retraces(
            [m], {"retrace": {"toy": _retrace_expectation()}})
        assert errors == []
        assert any("informational" in w for w in warnings)

    def test_missing_expectation_is_an_error(self):
        m = tracecheck.RetraceMeasurement(plan="toy", steps=4)
        errors, _ = tracecheck.compare_retraces([m], {"retrace": {}})
        assert any("no committed retrace expectation" in e
                   for e in errors)


class TestRetraceRules:
    """GL130–GL133: the static half of the retrace guard. '<string>'
    counts as a hot module, so the fixtures run through lint_source."""

    def test_gl130_churned_capture_fires(self):
        assert ids("""
            import jax
            def make():
                total = 0.0
                @jax.jit
                def f(x):
                    return x + total
                for sample in range(3):
                    total += sample
                return f
        """) == ["GL130"]

    def test_gl130_loop_variable_capture_fires(self):
        assert ids("""
            import jax
            def make():
                fns = []
                for i in range(3):
                    @jax.jit
                    def f(x):
                        return x + i
                    fns.append(f)
                return fns
        """) == ["GL130"]

    def test_gl130_setup_normalization_clean(self):
        # both assignments happen before the traced def: the capture is
        # stable by trace time (the sp_step/pipeline config pattern)
        assert ids("""
            import jax
            def make(cfg):
                mode = cfg.mode
                mode = mode or "default"
                @jax.jit
                def f(x):
                    return x if mode == "default" else -x
                return f
        """) == []

    def test_gl130_rebind_after_def_fires(self):
        assert ids("""
            import jax
            def make(cfg):
                scale = 1.0
                @jax.jit
                def f(x):
                    return x * scale
                scale = cfg.scale
                return f
        """) == ["GL130"]

    def test_gl130_stable_capture_clean(self):
        assert ids("""
            import jax
            def make():
                scale = 2.0
                @jax.jit
                def f(x):
                    return x * scale
                return f
        """) == []

    def test_gl131_shape_branch_fires(self):
        assert ids("""
            import jax
            @jax.jit
            def f(x):
                if x.shape[0] > 4:
                    return x * 2
                return x
        """) == ["GL131"]

    def test_gl131_len_branch_fires(self):
        assert ids("""
            import jax
            @jax.jit
            def f(x):
                while len(x) > 2:
                    x = x[:-1]
                return x
        """) == ["GL131"]

    def test_gl131_shape_guard_that_raises_clean(self):
        # static shape validation: traces once per shape like any jit,
        # but it is a guard, not a per-shape code path
        assert ids("""
            import jax
            @jax.jit
            def f(x):
                if x.shape[0] % 4 != 0:
                    raise ValueError("bad shape")
                return x
        """) == []

    def test_gl131_nonshape_branch_clean(self):
        assert ids("""
            import jax
            def run(f, flag, x):
                if flag:
                    return f(x)
                return x
        """) == []

    def test_gl132_literal_np_constant_fires(self):
        # the np call in a trace also trips GL102 (host sync) — both
        # diagnoses are correct, GL132 adds the weak-type-churn angle
        assert ids("""
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                c = np.array([1.0, 2.0])
                return x + c
        """) == ["GL102", "GL132"]

    def test_gl132_converting_traced_value_not_flagged(self):
        # np.asarray(x) of a traced value is GL102's host-sync
        # territory, not a per-call constant
        assert "GL132" not in ids("""
            import jax
            import numpy as np
            @jax.jit
            def f(x):
                return np.asarray(x)
        """)

    def test_gl133_mutable_static_default_fires(self):
        # the tuple default on g is hashable (clean); the list on h
        # fires GL133 at the jit site and GL104 at the def
        assert ids("""
            import jax
            def g(x, cfg=(1, 2)):
                return x
            gj = jax.jit(g, static_argnums=(1,))
            def h(x, cfg=[1, 2]):
                return x
            hj = jax.jit(h, static_argnums=(1,))
        """) == ["GL104", "GL133"]

    def test_gl133_decorator_form_fires(self):
        assert ids("""
            import jax
            from functools import partial
            @partial(jax.jit, static_argnames=("opts",))
            def h(x, opts={}):
                return x
        """) == ["GL133", "GL104"]

    def test_gl133_unhashable_literal_at_call_site_fires(self):
        assert ids("""
            import jax
            def g(x, n):
                return x
            gj = jax.jit(g, static_argnums=(1,))
            def run(x):
                return gj(x, [3, 4])
        """) == ["GL133"]

    def test_gl133_hashable_static_usage_clean(self):
        assert ids("""
            import jax
            def g(x, n):
                return x
            gj = jax.jit(g, static_argnums=(1,))
            def run(x):
                return gj(x, 3)
        """) == []


class TestGoldenAtomicity:
    """Satellite f: ``--regen`` across all layers must be all-or-nothing
    — a failure mid-batch leaves every committed golden untouched."""

    def test_partial_failure_leaves_goldens_untouched(self, tmp_path):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text('{"old": "a"}')
        b.write_text('{"old": "b"}')
        with pytest.raises(TypeError):
            golden.commit_goldens([
                (str(a), {"new": "a"}),
                (str(b), {"bad": object()}),  # not JSON-serializable
            ])
        assert json.loads(a.read_text()) == {"old": "a"}
        assert json.loads(b.read_text()) == {"old": "b"}
        assert list(tmp_path.glob("*.tmp")) == []

    def test_success_commits_every_golden(self, tmp_path):
        a = tmp_path / "a.json"
        b = tmp_path / "b.json"
        a.write_text('{"old": "a"}')
        written = golden.commit_goldens([
            (str(a), {"new": "a"}),
            (str(b), {"new": "b"}),
        ])
        assert written == [str(a), str(b)]
        assert json.loads(a.read_text()) == {"new": "a"}
        assert json.loads(b.read_text()) == {"new": "b"}
        assert list(tmp_path.glob("*.tmp")) == []

    def test_write_golden_single_file_atomic(self, tmp_path):
        p = tmp_path / "g.json"
        golden.write_golden(str(p), {"k": 1})
        assert json.loads(p.read_text()) == {"k": 1}
        assert not (tmp_path / "g.json.tmp").exists()

    def test_diff_file_format(self, tmp_path):
        out = tmp_path / "diff.txt"
        golden.write_diff_file(str(out), "graftlint perf diff",
                               ["plan toy: boom"], ["soft note"])
        text = out.read_text()
        assert text.startswith("# graftlint perf diff\n")
        assert "plan toy: boom" in text
        assert "# warnings" in text
        assert "soft note" in text


@pytest.mark.slow
class TestPerfMatrix:
    """Full plan matrix vs the committed perf_budgets.json (one AOT
    compile per plan plus the retrace execution — slow tier; the
    lint-perf CI job runs the same through the CLI)."""

    def test_all_plans_verify(self):
        errors, warnings = perf.run_perf_audit()
        assert errors == [], "\n".join(errors + warnings)

    def test_diff_out_written_on_ceiling_breach(self, tmp_path):
        budgets = perf.load_perf_budgets()
        budgets["provenance"]["jax"] = jax.__version__  # hard mode
        budgets["plans"]["dp"]["scoring_frac_ceiling"] = 0.0001
        broken = tmp_path / "perf_budgets.json"
        broken.write_text(json.dumps(budgets))
        out = tmp_path / "diff.txt"
        errors, _ = perf.run_perf_audit(
            plans=("dp",), budgets_path=str(broken),
            diff_out=str(out))
        assert errors
        text = out.read_text()
        assert "graftlint perf diff" in text
        assert "ceiling" in text

    def test_retrace_guard_dp_clean(self):
        errors, warnings = tracecheck.run_retrace_guard(plans=("dp",))
        assert errors == [], "\n".join(errors + warnings)
