"""Pallas TPU kernels, checked against the ref.py oracles: compiled on the
chip (``chip_smoke.py``), in interpret mode on the CPU (``tests/test_kernels.py``)."""

from . import ops, ref
from .ops import decode_attention, gemv, gemv_tiles, remote_first_order, rmsnorm

__all__ = ["ops", "ref", "gemv", "gemv_tiles", "decode_attention", "rmsnorm",
           "remote_first_order"]
