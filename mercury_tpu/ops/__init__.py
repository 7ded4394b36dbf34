from mercury_tpu.ops.mercury_kernels import (  # noqa: F401
    head_nll_pallas,
    head_nll_takes,
    input_moments_pallas,
    on_tpu,
    per_sample_nll_pallas,
    rope_heads_pallas,
    rope_heads_takes,
    score_and_draw_pallas,
)
