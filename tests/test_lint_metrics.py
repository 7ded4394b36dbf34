"""graftlint Layer M (metric-key registry auditor).

Layer M is exercised on synthetic package/registry/docs trees so every
finding class (GLM01/02/03) and every parsing subtlety (f-string skip,
brace families, fenced code blocks, the registry's own literals) is
pinned, then once against the real repo — which must be clean, since the
same check gates CI.
"""

import pytest

from mercury_tpu.lint.metrics import (
    documented_keys,
    emitted_keys,
    load_registry,
    run_metrics_check,
)


def write_tree(tmp_path, package=None, registry=None, docs=None):
    """Materialize a synthetic (package, registry, docs) triple; returns
    run_metrics_check-ready paths."""
    pkg = tmp_path / "pkg"
    pkg.mkdir(exist_ok=True)
    for name, src in (package or {}).items():
        (pkg / name).write_text(src)
    reg = tmp_path / "registry.py"
    reg.write_text(registry if registry is not None else
                   'METRIC_KEYS = {\n    "train/loss": "loss",\n}\n')
    doc = tmp_path / "API.md"
    doc.write_text(docs if docs is not None else "`train/loss` — loss\n")
    return [str(pkg)], str(reg), str(doc)


class TestLayerM:
    def test_clean_triple_passes(self, tmp_path):
        paths, reg, doc = write_tree(
            tmp_path, package={"a.py": 'KEY = "train/loss"\n'})
        errors, warnings = run_metrics_check(paths, reg, doc)
        assert errors == []
        assert warnings == []

    def test_glm01_unregistered_literal_is_error(self, tmp_path):
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": 'm = {"train/loss": 1, "train/bogus": 2}\n'})
        errors, _ = run_metrics_check(paths, reg, doc)
        assert len(errors) == 1
        assert "GLM01" in errors[0] and "train/bogus" in errors[0]
        assert "a.py:1" in errors[0]

    def test_fstring_fragments_are_not_keys(self, tmp_path):
        # f"{split}/eval_loss" must not be judged: the constant fragment
        # is a key suffix, not a key.
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": 'k = f"{split}/eval_loss"\n'
                             'j = f"train/dynamic_{i}"\n'})
        errors, _ = run_metrics_check(paths, reg, doc)
        assert errors == []

    def test_glm02_registered_but_undocumented_is_error(self, tmp_path):
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": 'KEY = "train/loss"\n'},
            registry=('METRIC_KEYS = {"train/loss": "l", '
                      '"obs/hidden": "h"}\n'),
            docs="`train/loss` and `obs/hidden` documented,\n")
        assert run_metrics_check(paths, reg, doc)[0] == []
        bare_doc = tmp_path / "bare.md"
        bare_doc.write_text("only `train/loss`\n")
        errors, _ = run_metrics_check(paths, reg, str(bare_doc))
        assert len(errors) == 1
        assert "GLM02" in errors[0] and "obs/hidden" in errors[0]

    def test_glm03_dead_registry_entry_is_warning_only(self, tmp_path):
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": "x = 1\n"},
            docs="`train/loss` documented\n")
        errors, warnings = run_metrics_check(paths, reg, doc)
        assert errors == []
        assert len(warnings) == 1
        assert "GLM03" in warnings[0] and "train/loss" in warnings[0]

    def test_docs_brace_families_expand(self, tmp_path):
        doc = tmp_path / "d.md"
        doc.write_text("`sampler/table_age_{min,mean,max}` summary\n")
        assert documented_keys(str(doc)) == {
            "sampler/table_age_min", "sampler/table_age_mean",
            "sampler/table_age_max"}

    def test_docs_fenced_code_blocks_stripped(self, tmp_path):
        # A fence would desync backtick pairing; keys inside one are not
        # glossary entries either way.
        doc = tmp_path / "d.md"
        doc.write_text("```json\n{\"train/loss\": 1}\n```\n"
                       "after the fence `perf/mfu` counts\n")
        assert documented_keys(str(doc)) == {"perf/mfu"}

    def test_registry_file_literals_are_not_emissions(self, tmp_path):
        # The registry defines keys; its literals must not count as uses
        # (GLM03 would otherwise never fire).
        paths, reg, doc = write_tree(
            tmp_path,
            package={"registry.py": 'METRIC_KEYS = {"train/loss": "l"}\n'},
            docs="`train/loss`\n")
        assert emitted_keys(paths) == {}

    def test_load_registry_rejects_computed_dict(self, tmp_path):
        reg = tmp_path / "r.py"
        reg.write_text("METRIC_KEYS = dict(x=1)\n")
        with pytest.raises(ValueError):
            load_registry(str(reg))
        reg.write_text("OTHER = {}\n")
        with pytest.raises(ValueError):
            load_registry(str(reg))

    def test_two_level_host_prof_keys_are_keys(self, tmp_path):
        # host/{min,max,spread}/* and prof/scope_frac/* are two levels
        # deep — KEY_RE must judge them (a typo'd deep key is GLM01).
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": 'k = "host/spread/step_time_s"\n'
                             'p = "prof/scope_frac/mercury_scoring"\n'},
            registry='METRIC_KEYS = {\n'
                     '    "host/spread/step_time_s": "spread",\n'
                     '    "prof/scope_frac/mercury_scoring": "frac",\n'
                     '}\n',
            docs="`host/spread/step_time_s` `prof/scope_frac/"
                 "mercury_scoring`\n")
        errors, warnings = run_metrics_check(paths, reg, doc)
        assert errors == []
        assert warnings == []

    def test_glm01_unregistered_prof_key(self, tmp_path):
        paths, reg, doc = write_tree(
            tmp_path,
            package={"a.py": 'k = "prof/scope_frac/mercury_typo"\n'})
        errors, _ = run_metrics_check(paths, reg, doc)
        assert len(errors) == 1
        assert "GLM01" in errors[0]
        assert "prof/scope_frac/mercury_typo" in errors[0]

    def test_real_registry_is_subset_of_docs(self):
        # Round-trip over the REAL triple: every registered key —
        # including the host/* and prof/* families added for cross-host
        # telemetry — has a docs-glossary entry.
        from mercury_tpu.lint import metrics as lm

        registry = load_registry(lm._default_registry_path())
        documented = documented_keys(lm._default_docs_path())
        assert set(registry) <= documented, \
            sorted(set(registry) - documented)
        for family in ("host/straggler_ratio", "host/spread/step_time_s",
                       "prof/scope_frac/unattributed", "prof/idle_frac"):
            assert family in registry

    def test_real_repo_is_clean(self):
        # The CI gate itself: the shipped package/registry/docs triple
        # must audit clean (warnings allowed — the f-string eval family).
        errors, warnings = run_metrics_check()
        assert errors == []
        for w in warnings:
            assert "GLM03" in w


class TestGLM04EventKinds:
    """Event-kind parity (GLM04): journal-emit first arguments vs
    ``EVENT_KINDS`` vs the OBSERVABILITY.md kind catalog — and the
    plane separation that keeps event kinds out of the metric scan."""

    REGISTRY = ('METRIC_KEYS = {"train/loss": "l"}\n'
                'EVENT_KINDS = {"supervisor/degrade": "descent"}\n')

    def tree(self, tmp_path, src, registry=None, event_docs=None):
        paths, reg, doc = write_tree(
            tmp_path, package={"a.py": src},
            registry=registry if registry is not None else self.REGISTRY,
            docs="`train/loss`\n")
        edoc = tmp_path / "OBSERVABILITY.md"
        edoc.write_text(event_docs if event_docs is not None
                        else "`supervisor/degrade` — one descent\n")
        return paths, reg, doc, str(edoc)

    def test_clean_quad_passes(self, tmp_path):
        paths, reg, doc, edoc = self.tree(
            tmp_path,
            'KEY = "train/loss"\n'
            'self._journal.emit("supervisor/degrade", 3)\n')
        errors, warnings = run_metrics_check(paths, reg, doc, edoc)
        assert errors == []
        assert warnings == []

    def test_unregistered_emit_is_error(self, tmp_path):
        paths, reg, doc, edoc = self.tree(
            tmp_path,
            'self._journal.emit("supervisor/degrade", 1)\n'
            'journal.emit("supervisor/typo_kind", 2)\n')
        errors, _ = run_metrics_check(paths, reg, doc, edoc)
        assert len(errors) == 1
        assert "GLM04" in errors[0] and "supervisor/typo_kind" in errors[0]
        assert "a.py:2" in errors[0]

    def test_wrapper_emit_call_is_detected(self, tmp_path):
        # The supervisor's call-site shape: a bound wrapper whose NAME
        # carries the journal marker (self._journal_emit).
        paths, reg, doc, edoc = self.tree(
            tmp_path,
            'KEY = "train/loss"\n'
            'self._journal_emit("supervisor/degrade", 1)\n')
        errors, warnings = run_metrics_check(paths, reg, doc, edoc)
        assert errors == []
        assert warnings == []

    def test_registered_undocumented_is_error(self, tmp_path):
        paths, reg, doc, edoc = self.tree(
            tmp_path,
            'self._journal.emit("supervisor/degrade", 1)\n',
            event_docs="no backticked catalog entry here\n")
        errors, _ = run_metrics_check(paths, reg, doc, edoc)
        assert len(errors) == 1
        assert "GLM04" in errors[0] and "supervisor/degrade" in errors[0]

    def test_registered_never_emitted_is_warning(self, tmp_path):
        paths, reg, doc, edoc = self.tree(
            tmp_path, 'x = "train/loss"\n')
        errors, warnings = run_metrics_check(paths, reg, doc, edoc)
        assert errors == []
        assert len(warnings) == 1
        assert "GLM04" in warnings[0] and "never" in warnings[0]

    def test_emit_args_excluded_from_metric_scan(self, tmp_path):
        # "supervisor/degrade" shares the slash grammar with metric keys
        # but is NOT registered in METRIC_KEYS: without the journal-emit
        # exclusion this would be a GLM01 false positive.
        paths, reg, doc, edoc = self.tree(
            tmp_path, 'self._journal.emit("supervisor/degrade", 1)\n')
        assert "supervisor/degrade" not in emitted_keys(paths)
        errors, _ = run_metrics_check(paths, reg, doc, edoc)
        assert errors == []

    def test_kind_comparisons_excluded_from_metric_scan(self, tmp_path):
        # Consumer side of the same plane: journal readers filter on
        # kind (obs/report.py) — comparison literals are not emissions.
        paths, reg, doc, edoc = self.tree(
            tmp_path,
            'ok = [e for e in events\n'
            '      if e.get("kind") == "supervisor/degrade"]\n'
            'if kind != "supervisor/degrade":\n'
            '    pass\n')
        assert "supervisor/degrade" not in emitted_keys(paths)
        errors, _ = run_metrics_check(paths, reg, doc, edoc)
        assert errors == []

    def test_missing_event_registry_tolerated(self, tmp_path):
        # A metric-only registry (no EVENT_KINDS literal) stays valid —
        # but any journal emission against it is then unregistered.
        paths, reg, doc, edoc = self.tree(
            tmp_path, 'x = "train/loss"\n',
            registry='METRIC_KEYS = {"train/loss": "l"}\n')
        assert run_metrics_check(paths, reg, doc, edoc) == ([], [])
        paths, reg, doc, edoc = self.tree(
            tmp_path, 'journal.emit("supervisor/degrade", 1)\n',
            registry='METRIC_KEYS = {"train/loss": "l"}\n')
        errors, _ = run_metrics_check(paths, reg, doc, edoc)
        assert len(errors) == 1 and "GLM04" in errors[0]

    def test_real_event_registry_covers_producers(self):
        # The shipped quad audits clean (the CI gate), and the kinds the
        # acceptance chain depends on are present end to end.
        from mercury_tpu.lint import metrics as lm
        from mercury_tpu.lint.metrics import (
            documented_event_kinds,
            emitted_event_kinds,
            load_event_registry,
        )

        kinds = load_event_registry(lm._default_registry_path())
        emitted = emitted_event_kinds(
            [lm._default_registry_path().rsplit("/", 2)[0]])
        documented = documented_event_kinds(
            lm._default_event_docs_path())
        assert set(emitted) <= set(kinds), \
            sorted(set(emitted) - set(kinds))
        assert set(kinds) <= documented, \
            sorted(set(kinds) - documented)
        for kind in ("supervisor/degrade", "supervisor/probe_failed",
                     "supervisor/exhausted", "fault/fired",
                     "anomaly/triggered", "checkpoint/written"):
            assert kind in kinds and kind in emitted, kind
