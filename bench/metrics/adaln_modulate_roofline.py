"""Kernels: the adaLN kernels' (modulate and gate_residual) share (%) of
their roofline (see _common.roofline and bench/costs/adaln_modulate.py)."""

from bench.metrics._common import roofline


def read(rec):
    return roofline(rec, "adaln_modulate")
