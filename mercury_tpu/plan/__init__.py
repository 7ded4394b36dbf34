"""mercury_tpu.plan — automatic parallelism-plan selection.

The auto-planner compiles the committed graftlint cost model (Layer P
per-scope FLOP/byte attribution in ``lint/perf_budgets.json``, Layer 3
``memory_analysis()`` footprints in ``lint/shard_budgets.json``) plus an
analytic collective-latency model into a ranked plan decision:
``TrainConfig(plan="auto")`` resolves through ``plan.auto.select_plan``
at trainer construction, and ``restore_elastic`` re-plans when the
(W, L) mesh changes.

Everything here is stdlib-only (no jax import): the planner scores from
committed goldens, so CI's jax-free leg can run it.
"""

from mercury_tpu.plan.auto import (  # noqa: F401
    PLAN_KNOBS,
    PlanCandidate,
    PlanDecision,
    resolve_plan_config,
    select_plan,
)
from mercury_tpu.plan.latency import (  # noqa: F401
    LINK_BANDWIDTH_BYTES_PER_S,
    all_gather_cost_s,
    collective_cost_s,
    link_bandwidth,
    reduce_scatter_cost_s,
    ring_allreduce_cost_s,
)
