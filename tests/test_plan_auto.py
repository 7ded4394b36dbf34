"""Auto-planner (``mercury_tpu/plan/auto.py``, DESIGN.md §16): plan
selection compiled from the committed Layer P / Layer 3 goldens.

The fast half is pure scoring logic — deterministic ranking from the
committed json, hard memory-budget exclusion, machine-readable rejection
reasons, the jax-free import contract, and the trainer-facing config
resolution. The slow half executes: a Trainer resolving ``plan="auto"``
end-to-end, the W=8→4→8 elastic round trip with journaled re-plans that
must replay Layer S-conformant, and the honesty check — the planner's
pick must land in the top-2 of *measured* steps/s across the plan
matrix (the audit builders' own step programs, timed)."""

import json
import subprocess
import sys
import textwrap

import pytest

from mercury_tpu.config import TrainConfig
from mercury_tpu.plan.auto import (
    PLAN_KNOBS,
    PLAN_NAMES,
    load_cost_model,
    resolve_plan_config,
    select_plan,
)
from mercury_tpu.plan.latency import (
    LINK_BANDWIDTH_BYTES_PER_S,
    all_gather_cost_s,
    collective_cost_s,
    link_bandwidth,
    reduce_scatter_cost_s,
    ring_allreduce_cost_s,
)

BUDGET_6MB = 6_000_000


# --------------------------------------------------------------------------
# latency model
# --------------------------------------------------------------------------

class TestLatencyModel:
    def test_ring_allreduce_formula(self):
        # 2·(W−1)/W · bytes / bw, exactly.
        bw = LINK_BANDWIDTH_BYTES_PER_S["cpu"]
        assert ring_allreduce_cost_s(1000.0, 4, "cpu") == pytest.approx(
            2.0 * 3 / 4 * 1000.0 / bw)
        assert all_gather_cost_s(1000.0, 4, "cpu") == pytest.approx(
            0.75 * 1000.0 / bw)
        assert reduce_scatter_cost_s(1000.0, 4, "cpu") == \
            all_gather_cost_s(1000.0, 4, "cpu")

    def test_single_device_is_free(self):
        assert ring_allreduce_cost_s(1e9, 1, "cpu") == 0.0
        assert all_gather_cost_s(1e9, 1, "tpu v4") == 0.0

    def test_bandwidth_longest_prefix_match(self):
        assert link_bandwidth("TPU v5 lite") == \
            LINK_BANDWIDTH_BYTES_PER_S["tpu v5 lite"]
        # "tpu v5p" must win over the shorter "tpu v5..." family entries.
        assert link_bandwidth("TPU v5p chip") == \
            LINK_BANDWIDTH_BYTES_PER_S["tpu v5p"]
        assert link_bandwidth("") == LINK_BANDWIDTH_BYTES_PER_S["cpu"]
        # An untabulated accelerator is an error, never the cpu figure.
        with pytest.raises(ValueError, match="quantum abacus"):
            link_bandwidth("quantum abacus")

    def test_collective_dispatch_by_hlo_kind(self):
        ar = collective_cost_s("all-reduce", 1000.0, 4, "cpu")
        ag = collective_cost_s("all-gather", 1000.0, 4, "cpu")
        assert ar == ring_allreduce_cost_s(1000.0, 4, "cpu")
        assert ag == all_gather_cost_s(1000.0, 4, "cpu")
        # Unknown collective kinds take the all-gather (single-pass) cost.
        assert collective_cost_s("mystery-op", 1000.0, 4, "cpu") == ag


# --------------------------------------------------------------------------
# selection from the committed goldens
# --------------------------------------------------------------------------

class TestSelectPlan:
    def test_plan_matrix_mirrors_audit(self):
        from mercury_tpu.lint import audit
        assert PLAN_NAMES == audit.PLAN_NAMES

    def test_goldens_cover_the_matrix(self):
        cm = load_cost_model()
        assert set(PLAN_NAMES) <= set(cm["perf"]["plans"])
        assert set(PLAN_NAMES) <= set(cm["shard"]["plans"])

    def test_untabulated_accelerator_raises(self):
        """The planner prices compute at the tabulated peak; a TPU it
        does not know is an error, not the CPU stand-in rate."""
        with pytest.raises(ValueError, match="TPU v9"):
            select_plan(model="smallcnn", world_size=8,
                        device_kind="TPU v9 hyper")

    def test_unbounded_ranking_is_deterministic(self):
        d1 = select_plan(model="smallcnn", world_size=8, device_kind="cpu")
        d2 = select_plan(model="smallcnn", world_size=8, device_kind="cpu")
        assert [c.name for c in d1.candidates] == \
            [c.name for c in d2.candidates]
        assert len(d1.candidates) == len(PLAN_NAMES)
        # The off-step refresh plans (zero scoring ops in the fused step)
        # must outrank every scoring plan on equal goldens.
        assert d1.selected == "async"
        assert d1.feasible[1].name == "device_scorer"  # tie, name-broken

    def test_every_feasible_candidate_is_scored(self):
        d = select_plan(model="smallcnn", world_size=8, device_kind="cpu")
        for c in d.feasible:
            assert c.est_step_s and c.est_step_s > 0
            assert c.compute_s is not None and c.collective_s is not None
            assert c.est_steps_per_s == pytest.approx(1.0 / c.est_step_s)
            assert not c.reasons

    def test_memory_budget_hard_exclusion(self):
        # A budget below dp's committed peak must exclude dp even though
        # it scores — a memory-infeasible plan is provably out, never
        # merely outranked.
        cm = load_cost_model()
        dp_peak = cm["shard"]["plans"]["dp"]["memory"][
            "peak_estimate_in_bytes"]
        d = select_plan(model="smallcnn", world_size=8,
                        memory_budget_bytes=dp_peak - 1, device_kind="cpu")
        dp = d.candidate("dp")
        assert not dp.feasible and dp.memory_status == "over_budget"
        assert "dp" not in [c.name for c in d.feasible]
        reason = next(r for r in dp.reasons if r["rule"] == "memory_budget")
        assert reason["peak_bytes"] > reason["budget_bytes"] == dp_peak - 1

    def test_zero_footprint_scales_with_world_size(self):
        # The deterministic budget switch the CI elastic smoke rides:
        # ZeRO's sharded footprint fits 6 MB at W=8 (scaled ~W_ref/W) and
        # is hard-excluded at W=4, so the selection provably moves.
        b8 = select_plan(model="smallcnn", world_size=8,
                         memory_budget_bytes=BUDGET_6MB, device_kind="cpu")
        b4 = select_plan(model="smallcnn", world_size=4,
                         memory_budget_bytes=BUDGET_6MB, device_kind="cpu")
        assert b8.selected == "zero"
        assert b4.selected == "hs"
        z8, z4 = b8.candidate("zero"), b4.candidate("zero")
        assert z8.feasible and not z4.feasible
        assert z4.memory_bytes == 2 * z8.memory_bytes
        assert any(r["rule"] == "memory_budget" for r in z4.reasons)

    def test_rejection_reasons_are_machine_readable(self):
        d = select_plan(model="smallcnn", world_size=8, process_count=2,
                        device_kind="cpu",
                        constraints={"augmentation": "iid", "cutout": False})
        rules = {c.name: [r["rule"] for r in c.reasons]
                 for c in d.candidates}
        assert "model_family" in rules["sp"]       # CNN can't take sp/pp
        assert "config_surface" in rules["pp"]     # no TrainConfig knobs
        assert "single_controller" in rules["async"]       # 2 processes
        assert "single_controller" in rules["device_scorer"]
        assert "ingest_precondition" in rules["hs_fused"]  # iid augment

    def test_mesh_shape_rules_on_transformer(self):
        d = select_plan(model="transformer", world_size=2,
                        require_config_addressable=False, device_kind="cpu")
        assert "mesh_shape" in [r["rule"]
                                for r in d.candidate("sp").reasons]
        d3 = select_plan(model="transformer", world_size=3,
                         require_config_addressable=False, device_kind="cpu")
        assert "mesh_shape" in [r["rule"]
                                for r in d3.candidate("pp").reasons]
        # At W=4 both become mesh-feasible for the transformer family.
        d4 = select_plan(model="transformer", world_size=4,
                         require_config_addressable=False, device_kind="cpu")
        assert d4.candidate("sp").feasible and d4.candidate("pp").feasible

    def test_unavailable_memory_stays_feasible(self):
        # lint/memory.py's degraded {"unavailable": ...} entry: "no data"
        # must be distinguishable from "fits" — the plan stays in the
        # feasible set with the gap recorded, never silently dropped.
        cm = load_cost_model()
        cm = json.loads(json.dumps(cm))  # deep copy before mutating
        cm["shard"]["plans"]["dp"]["memory"] = {"unavailable": "no stats"}
        d = select_plan(model="smallcnn", world_size=8,
                        memory_budget_bytes=1_000, device_kind="cpu",
                        cost_model=cm)
        dp = d.candidate("dp")
        assert dp.feasible and dp.memory_status == "unavailable"
        assert dp.memory_bytes is None

    def test_unknown_plan_raises(self):
        with pytest.raises(ValueError, match="unknown plan"):
            select_plan(plans=["dp", "warp_drive"])

    def test_decision_detail_is_json_safe(self):
        d = select_plan(model="smallcnn", world_size=8, device_kind="cpu")
        detail = json.loads(json.dumps(d.detail()))
        assert detail["selected"] == d.selected
        assert detail["candidates_considered"] == len(PLAN_NAMES)
        assert [row["plan"] for row in detail["table"]] == \
            [c.name for c in d.candidates]

    def test_package_import_is_jax_free(self):
        # The planner must score on a jax-less host (CI's auto-planner
        # unit leg) — prove it by poisoning the import, not by trusting
        # the import graph.
        code = textwrap.dedent("""
            import builtins
            real = builtins.__import__
            def guard(name, *a, **kw):
                if name == "jax" or name.startswith("jax."):
                    raise ImportError(f"jax import blocked: {name}")
                return real(name, *a, **kw)
            builtins.__import__ = guard
            from mercury_tpu.plan.auto import select_plan
            d = select_plan(model="smallcnn", world_size=8,
                            device_kind="cpu")
            assert d.selected == "async", d.selected
            print(d.selected)
        """)
        out = subprocess.run([sys.executable, "-c", code],
                             capture_output=True, text=True, timeout=120)
        assert out.returncode == 0, out.stderr
        assert out.stdout.strip() == "async"


# --------------------------------------------------------------------------
# config resolution
# --------------------------------------------------------------------------

class TestResolvePlanConfig:
    def _cfg(self, **kw):
        base = dict(model="smallcnn", world_size=8, num_epochs=1)
        base.update(kw)
        return TrainConfig(**base)

    def test_empty_plan_is_untouched(self):
        cfg = self._cfg()
        out, decision = resolve_plan_config(cfg, device_kind="cpu")
        assert out is cfg and decision is None

    def test_auto_applies_winner_knobs(self):
        out, decision = resolve_plan_config(self._cfg(plan="auto"),
                                            device_kind="cpu")
        assert decision.selected == "async"
        assert out.sampler == "scoretable"
        assert out.refresh_mode == "async"
        assert out.scorer_backend == "host"
        assert out.plan == "auto"  # sticky: restore_elastic re-plans on it

    def test_budget_changes_the_resolution(self):
        out, decision = resolve_plan_config(
            self._cfg(plan="auto", plan_memory_budget_bytes=BUDGET_6MB),
            device_kind="cpu")
        assert decision.selected == "zero" and out.zero_sharding

    def test_forced_plan_applies_verbatim_and_still_scores(self):
        out, decision = resolve_plan_config(self._cfg(plan="zero"),
                                            device_kind="cpu")
        assert out.zero_sharding and decision.selected == "zero"
        # The table still shows where the forced plan ranked.
        assert len(decision.candidates) == len(PLAN_NAMES)

    def test_forced_plan_knob_sets_are_complete(self):
        # Every config-addressable plan must resolve through TrainConfig
        # without raising (knob names drift is a construction-time error).
        for name in PLAN_KNOBS:
            out, decision = resolve_plan_config(self._cfg(plan=name),
                                                device_kind="cpu")
            assert decision.selected == name

    def test_unknown_plan_name_rejected(self):
        with pytest.raises(ValueError, match="not resolvable"):
            resolve_plan_config(self._cfg(plan="warp_drive"),
                                device_kind="cpu")

    def test_no_feasible_plan_is_fatal_with_table(self):
        with pytest.raises(RuntimeError, match="no feasible plan"):
            resolve_plan_config(
                self._cfg(plan="auto", plan_memory_budget_bytes=1),
                device_kind="cpu")


# --------------------------------------------------------------------------
# canonical re-export + report rendering + lint/memory degradation
# --------------------------------------------------------------------------

class TestSurfaces:
    def test_collectives_reexports_the_latency_model(self):
        from mercury_tpu.parallel import collectives
        assert collectives.ring_allreduce_cost_s is ring_allreduce_cost_s
        assert collectives.link_bandwidth is link_bandwidth
        assert collectives.LINK_BANDWIDTH_BYTES_PER_S \
            is LINK_BANDWIDTH_BYTES_PER_S

    def test_report_renders_plan_selection_section(self):
        from mercury_tpu.obs.report import _plan_selection_blocks
        d = select_plan(model="smallcnn", world_size=8, device_kind="cpu")
        events = [
            {"kind": "plan/selected", "step": -1, "detail": d.detail()},
            {"kind": "elastic/replan", "step": 4,
             "detail": {"w_old": 8, "w_new": 4, "plan_old": "async",
                        "plan_new": "async", "changed": False,
                        "old_table": d.table(), "new_table": d.table()}},
        ]
        blocks = _plan_selection_blocks(events)
        assert ("h", 2, "Plan selection") in blocks
        assert ("h", 3, "Elastic re-plans") in blocks
        tables = [b for b in blocks if b[0] == "table"]
        assert len(tables) == 2  # construction decision + re-plan table
        assert any("async" in row for row in tables[0][2])

    def test_report_plan_section_absent_without_events(self):
        from mercury_tpu.obs.report import _plan_selection_blocks
        assert _plan_selection_blocks(
            [{"kind": "fault/fired", "detail": {}}]) == []

    def test_memory_profile_degrades_to_named_entry(self):
        from mercury_tpu.lint.memory import compare_memory, memory_profile

        class Raises:
            def memory_analysis(self):
                raise NotImplementedError("no stats on this backend")

        class ReturnsNone:
            def memory_analysis(self):
                return None

        prof = memory_profile(Raises())
        assert set(prof) == {"unavailable"}
        assert "NotImplementedError" in prof["unavailable"]
        assert set(memory_profile(ReturnsNone())) == {"unavailable"}
        # The ratchet treats an unavailable side as no-data: no findings.
        recorded = {"peak_estimate_in_bytes": 100}
        errors, warnings = compare_memory("dp", recorded, prof)
        assert errors == [] and warnings == []
        errors, warnings = compare_memory("dp", prof, recorded)
        assert errors == [] and warnings == []


# --------------------------------------------------------------------------
# slow: the planner against the real Trainer and the measured matrix
# --------------------------------------------------------------------------

def _cfg(world, tag, tmp, **kw):
    base = dict(
        model="smallcnn", dataset="synthetic", world_size=world,
        batch_size=8, presample_batches=2, num_epochs=1,
        steps_per_epoch=4, eval_every=0, log_every=1, heartbeat_every=0,
        checkpoint_every=0, compute_dtype="float32", seed=0,
        plan="auto", refresh_size=8, scorer_workers=1, snapshot_every=2,
        checkpoint_dir=str(tmp / "ckpt"), log_dir=str(tmp / tag))
    base.update(kw)
    return TrainConfig(**base)


def _journal(tmp, tag):
    from mercury_tpu.obs.events import read_journal
    return read_journal(str(tmp / tag / "events.h0.jsonl"))


@pytest.mark.slow
class TestTrainerIntegration:
    def test_trainer_resolves_auto_and_journals_decision(self, tmp_path):
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train.trainer import Trainer

        with Trainer(_cfg(4, "run", tmp_path),
                     mesh=host_cpu_mesh(4)) as tr:
            assert tr.config.refresh_mode == "async"
            assert tr._plan_decision.selected == "async"
            tr.fit()
        sel = [e for e in _journal(tmp_path, "run")
               if e["kind"] == "plan/selected"]
        assert len(sel) == 1
        detail = sel[0]["detail"]
        assert detail["selected"] == "async"
        assert detail["candidates_considered"] == len(PLAN_NAMES)
        recs = [json.loads(line) for line in
                open(tmp_path / "run" / "metrics.jsonl")]
        last = recs[-1]
        assert last["plan/candidates_considered"] == float(len(PLAN_NAMES))
        assert last["plan/replan_count"] == 0.0
        # The supervisor-free status surface still reports the decision
        # through bench/scrape consumers via _plan_facts.
        facts = tr._plan_facts()
        assert facts["selected"] == "async" and facts["replans"] == 0

    def test_elastic_replan_roundtrip_is_journaled_and_conformant(
            self, tmp_path):
        """W=8→4→8 with plan="auto": every restore across a world-size
        change journals an elastic/replan with both scored tables, state
        carries per the Layer E policies (elastic_restore is the same
        code path test_elastic.py pins), and each stage's journal must
        replay with ZERO Layer S conformance violations."""
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train.trainer import Trainer

        ckpt = str(tmp_path / "ckpt")
        with Trainer(_cfg(8, "w8", tmp_path),
                     mesh=host_cpu_mesh(8)) as tr:
            tr.fit()
            tr.save()
            step8 = int(tr.state.step)

        with Trainer(_cfg(4, "w4", tmp_path),
                     mesh=host_cpu_mesh(4)) as tr:
            tr.restore_elastic(ckpt, step=step8)
            tr.fit()
            tr.save()
            step4 = int(tr.state.step)
            assert step4 > step8
        ev4 = _journal(tmp_path, "w4")
        rp = [e for e in ev4 if e["kind"] == "elastic/replan"]
        assert len(rp) == 1, [e["kind"] for e in ev4]
        detail = rp[0]["detail"]
        assert detail["w_old"] == 8 and detail["w_new"] == 4
        assert detail["plan_old"] and detail["plan_new"]
        assert detail["old_table"] and detail["new_table"]
        assert rp[0]["step"] == step8
        recs = [json.loads(line) for line in
                open(tmp_path / "w4" / "metrics.jsonl")]
        assert recs[-1]["plan/replan_count"] == 1.0

        with Trainer(_cfg(8, "w8b", tmp_path),
                     mesh=host_cpu_mesh(8)) as tr:
            tr.restore_elastic(ckpt, step=step4)
            tr.fit()
        rpb = [e for e in _journal(tmp_path, "w8b")
               if e["kind"] == "elastic/replan"]
        assert len(rpb) == 1 and rpb[0]["detail"]["w_old"] == 4

        for tag in ("w8", "w4", "w8b"):
            out = subprocess.run(
                [sys.executable, "-m", "mercury_tpu.lint.control",
                 str(tmp_path / tag)],
                capture_output=True, text=True, timeout=300)
            assert out.returncode == 0, \
                f"{tag}: {out.stdout}\n{out.stderr}"

    def test_forced_plan_restore_does_not_replan(self, tmp_path):
        """A concrete (non-auto) plan is the user's call — an elastic
        restore must carry it silently, never journal a re-plan against
        a decision the user overrode."""
        from mercury_tpu.parallel.mesh import host_cpu_mesh
        from mercury_tpu.train.trainer import Trainer

        ckpt = str(tmp_path / "ckpt")
        with Trainer(_cfg(4, "a", tmp_path, plan="dp"),
                     mesh=host_cpu_mesh(4)) as tr:
            tr.fit()
            tr.save()
            step = int(tr.state.step)
        with Trainer(_cfg(8, "b", tmp_path, plan="dp"),
                     mesh=host_cpu_mesh(8)) as tr:
            tr.restore_elastic(ckpt, step=step)
        kinds = {e["kind"] for e in _journal(tmp_path, "b")}
        assert "elastic/replan" not in kinds
        assert "elastic/reshard_end" in kinds


@pytest.mark.slow
@pytest.mark.thread_leak_ok  # audit builders park trainer helpers by design
class TestPredictionHonesty:
    def test_auto_selection_within_top2_of_measured(self):
        """The acceptance bar: execute the plan matrix's own step
        programs (the audit builders — the exact constructions Layer
        2/3/P measure) for every plan the planner can select among on
        this model, and the planner's pick must land in the top-2 by
        measured steps/s. sp/pp run a different model family (toy
        transformer), so steps/s is not comparable across them — the
        measured set is the feasible (config-addressable, same-model)
        matrix, which is exactly the planner's decision space. async and
        device_scorer run the identical zero-scoring-ops program, so the
        bar is robust to CPU timing noise between the two."""
        import time

        import jax
        import jax.numpy as jnp

        from mercury_tpu.lint import audit

        audit.ensure_cpu_devices(8)
        decision = select_plan(model="smallcnn", world_size=2,
                               device_kind="cpu")
        feasible = [c.name for c in decision.feasible]
        measured = {}
        for name in feasible:
            step, args, _config = audit._BUILDERS[name]()
            state = args[0]

            def make_rest():
                # The hs builders hand the streamed slab as a trace
                # template; materialize it (donated per call, so fresh
                # each time — values are irrelevant to timing).
                return tuple(
                    jnp.zeros(a.shape, a.dtype)
                    if isinstance(a, jax.ShapeDtypeStruct) else a
                    for a in args[1:])

            def run_once(state):
                out = step(state, *make_rest())
                new_state = out[0] if isinstance(out, tuple) else out
                jax.block_until_ready(new_state)
                return new_state

            state = run_once(state)   # compile + warm
            state = run_once(state)
            times = []
            for _ in range(5):
                t0 = time.perf_counter()
                state = run_once(state)
                times.append(time.perf_counter() - t0)
            measured[name] = 1.0 / min(times)

        ranked = sorted(measured, key=measured.get, reverse=True)
        assert decision.selected in ranked[:2], (
            f"planner chose {decision.selected}, measured ranking {ranked} "
            f"({ {k: round(v, 1) for k, v in measured.items()} })")
