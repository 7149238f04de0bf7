"""Batch-tile heuristics and VMEM budgeting shared by every kernel wrapper.

Each DoT kernel owns a (TB, m)-shaped block of every operand in VMEM; the
only tunable is TB, the batch tile.  The heuristic keeps the kernel's live
working set inside a fixed fraction of VMEM:

    TB * m * live_u32_arrays * 4 bytes  <=  TARGET_WORKING_SET_BYTES

``live_u32_arrays`` is the per-kernel count of simultaneously-live
(TB, ~m) uint32 arrays (operands + accumulator + normalize temps), a
static property of the kernel body.  The previous per-ops magic numbers
(64k/32k/16k words) were exactly this formula with live = 6 / 12 / 24;
they are now stated as such in one place.

The heuristic is the default; ``common.autotune`` can override it with a
measured tile when REPRO_AUTOTUNE is set (see that module).
"""
from __future__ import annotations

VMEM_BYTES = 16 * 1024 * 1024          # per-core VMEM on current TPUs
TARGET_WORKING_SET_BYTES = 3 * VMEM_BYTES // 32   # ~1.5 MB: leave room for
#   double-buffered input/output blocks and compiler temps.

MIN_TILE = 8                            # one VPU sublane group
DEFAULT_MAX_TILE = 512


def budget_words(live_u32_arrays: int,
                 working_set_bytes: int = TARGET_WORKING_SET_BYTES) -> int:
    """Max TB*m uint32 words per live array under the working-set target."""
    return working_set_bytes // (4 * max(1, live_u32_arrays))


def batch_tile(m: int, batch: int, *, budget: int,
               max_tile: int = DEFAULT_MAX_TILE,
               min_tile: int = MIN_TILE) -> int:
    """Heuristic batch tile for a kernel over (batch, m) digit arrays.

    A multiple of ``min_tile``: the TPU compiler only accepts a block
    whose sublane dimension is a multiple of 8 (or the whole array)."""
    tb = max(min_tile, min(max_tile, budget // max(min_tile, m)))
    tb = min(tb, max(min_tile, batch))
    return max(min_tile, tb // min_tile * min_tile)
