"""Kernels: flash attention's share (%) of its roofline (see
_common.roofline and bench/costs/flash_attention.py)."""

from bench.metrics._common import roofline


def read(rec):
    return roofline(rec, "flash_attention")
