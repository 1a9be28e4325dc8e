"""Pallas TPU kernels for the paper's compute hot-spot (Block-Shotgun)."""
from repro.kernels.shotgun_block import (BLOCK, TILE_N, auto_tile_n,
                                         fused_shotgun_rounds)
from repro.kernels.ops import block_shotgun_solve, pad_problem
