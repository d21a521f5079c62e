"""Denoiser eval, whole step: useful model FLOPs (busy slot-ticks, from the
program's serve_active_slot_ticks counter, x rows per slot x FLOPs of one
row-eval counted from the shapes by the configuration's own FLOP module,
bench/costs/<config "flops">.py) / window seconds / the chip's bf16 peak
(%). Idle slots' rows are computed but not counted."""

from bench.cell import cost_module
from bench.metrics._common import counter


def read(rec):
    if rec.peaks is None:
        return None
    w = rec.window
    busy = counter(w, "serve_active_slot_ticks")
    flops = (busy * rec.rows_per_slot
             * cost_module(rec.config["flops"]).flops_per_row_eval(
                 rec.config["model"]))
    return 100.0 * flops / (w.t1 - w.t0) / rec.peaks["bf16_flops"]
