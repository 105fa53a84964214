"""Shared scheduler defaults — the single source of truth for tuned values.

This module is imported by BOTH sides of the stack (`repro.core` below the
facade, kernels/serving above it), so it must stay dependency-free: no
numpy, no jax, no intra-repo imports. That is what lets `core/policies.py`
import the constant without a circular import through the `repro.sched`
package init.
"""

# The paper evaluates iCh at eps in {25%, 33%, 50%} (Table 2) and finds the
# method insensitive within the band (eq. 10, Fig. 7); 33% is the midpoint
# the TPU schedule-construction layer was tuned with (DESIGN.md §2: the band
# edge mu*(1+eps) picks the tile width) and is what every kernel op shipped
# with. It is now the one default everywhere — the runtime policy
# (`core/policies.py:ich`), schedule construction (`core/tiling.py`), the
# kernel wrappers, the MoE balancer, and the serving engine all import it.
ICH_EPS = 0.33

# Segment slots per tile (R) for constructed schedules: 8 keeps the one-hot
# epilogue matmul (R, R) tiny while giving splitting enough slots to spread
# a heavy item (DESIGN.md §2.5).
ROWS_PER_TILE = 8

# Tile-width clamp for `ich_tile_width` and `gather_width` (work units per
# segment slot).
MIN_WIDTH = 8
MAX_WIDTH = 512

# Tiles per kernel superstep (B): each grid step of a worker-sharded ich_*
# kernel processes B tiles at once (a (B*R, W) payload block), amortizing
# the per-step dispatch/prefetch overhead over B tiles (DESIGN.md §2.6).
SUPERSTEP = 8

# Measured-cost feedback (DESIGN.md §2.7). REFINE_BLEND is the weight of
# the observed running mean against the a-priori estimate once an item has
# been observed at least once: 1.0 trusts measurements fully (the paper's
# posture — iCh's whole premise is that the runtime signal beats the
# estimate), lower values damp noisy single observations. Items never
# observed always keep their prior.
REFINE_BLEND = 1.0

# MoE expert dispatch (DESIGN.md §2.8): per-expert capacity is the chunk-
# size analogue, so its knobs live with the scheduler defaults and are
# imported by BOTH the in-graph layer (models/moe.py) and the host-side
# dispatch planner (sched/moe.py) — one source of truth keeps the two
# paths bit-identical at equal capacity.
MOE_CAPACITY_FACTOR = 1.25   # C_base = ceil(K * T * factor / E)
MOE_CMAX_FACTOR = 2.0        # compiled expert buffer = factor * C_base
MOE_MIN_CAPACITY = 4         # capacity floor (tiny decode pools)
# cap_scale (the d_i array) is clipped to the materializable range: the
# compiled buffer is C_max = MOE_CMAX_FACTOR * C_base, so scale can never
# usefully exceed it, and 0.25 keeps cold experts warm enough to recover.
MOE_CAP_SCALE_MIN = 0.25
MOE_CAP_SCALE_MAX = 2.0
