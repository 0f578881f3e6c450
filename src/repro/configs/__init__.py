"""Architecture configs. Importing this package populates the registry.

Assigned archs (10) + the paper's own validation model (qwen36-35b-a3b).
"""
from repro.configs import (  # noqa: F401
    dbrx_132b,
    musicgen_large,
    phi3_mini_3_8b,
    pixtral_12b,
    qwen2_moe_a2_7b,
    qwen3_4b,
    qwen36_35b_a3b,
    recurrentgemma_2b,
    starcoder2_3b,
    starcoder2_7b,
    xlstm_350m,
)
from repro.configs.reduced import cut_depth, reduce_for_smoke  # noqa: F401
from repro.configs.shapes import SHAPES, applicable_shapes, shape_applies  # noqa: F401

ASSIGNED_ARCHS = (
    "starcoder2-7b",
    "starcoder2-3b",
    "qwen3-4b",
    "phi3-mini-3.8b",
    "qwen2-moe-a2.7b",
    "dbrx-132b",
    "xlstm-350m",
    "recurrentgemma-2b",
    "pixtral-12b",
    "musicgen-large",
)
PAPER_ARCH = "qwen36-35b-a3b"
ALL_ARCHS = ASSIGNED_ARCHS + (PAPER_ARCH,)
