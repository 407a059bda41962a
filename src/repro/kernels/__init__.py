"""Pallas TPU kernels for the paper's compute hot-spots.

The paper's performance section optimizes exactly one thing: distance
evaluation during graph traversal (exact L2 + RaBitQ/FastScan approximate).
Hence two kernel families:

    l2dist/   — batched/fused-gather squared-L2 (exact tier)
    bitdot/   — packed 1-bit RaBitQ code contraction + fused estimator
                (approximate tier; TPU-native FastScan replacement)

Each provides  <name>.py (pl.pallas_call + BlockSpec),  ops.py (jitted
wrapper w/ CPU interpret fallback),  ref.py (pure-jnp oracle).
"""

from .l2dist.ops import batched_l2, gather_l2  # noqa: F401
from .bitdot.ops import bitdot, fused_estimate  # noqa: F401
