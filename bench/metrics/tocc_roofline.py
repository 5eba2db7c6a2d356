"""Bytes and peaks behind ``tocc_hbm_share.reads``: the least the stacked
T-occurrence program (``jit__tocc_stack``, ``repro.runtime.spmd``) has
to move, and the HBM bandwidth of the chips it runs on."""

# published HBM bandwidth per chip, by ``device_kind`` (Google Cloud
# documentation, "TPU v5e": 819 GB/s)
PEAK_HBM_BYTES_S = {"TPU v5 lite": 819e9}

POSITION_BYTES = 8          # int64 posting positions
COUNT_BYTES = 4             # int32 count cells

PROGRAM = "jit__tocc_stack"


def min_bytes(hit_positions: int, count_cells: int) -> int:
    """A lower bound on the bytes the program moves: each gathered hit
    position read once, and each count cell read and written once (the
    segment bounds, the mask written out and the scatter's index traffic
    are left out)."""
    return hit_positions * POSITION_BYTES + 2 * count_cells * COUNT_BYTES
