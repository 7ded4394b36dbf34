from mercury_tpu.ops.mercury_kernels import (  # noqa: F401
    on_tpu,
    per_sample_nll_pallas,
    score_and_draw_pallas,
)
