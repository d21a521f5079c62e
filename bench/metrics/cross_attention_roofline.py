"""Kernels: the cross-attention calls' share (%) of their roofline alone:
the least time of the traced flash-attention ops that make a cross call
(a listed call whose query and key lengths come from different `model`
keys, told apart by `flash_attention.call_of`), each charged with that
call's entry of `flash_attention.per_call`, over the device time they took.
No value where the configuration lists no cross call or the trace cannot
tell the calls apart."""

from bench.cell import cost_module


def read(rec):
    if rec.trace is None or rec.peaks is None:
        return None
    cost = cost_module("flash_attention")
    calls = rec.config["attention"]
    cross = {i for i, c in enumerate(calls) if c["q"] != c["kv"]}
    if not cross:
        return None
    each = cost.per_call("attention", rec.config, rec.rows_per_slot)
    ops = {n: op for n, op in rec.trace["ops"].items()
           if cost.kind(n) == "attention"}
    least = took = 0.0
    for name, op in ops.items():
        i = cost.call_of(name, list(ops), rec.config)
        if i is None:
            return None
        if i not in cross:
            continue
        flops, nbytes = each[i]
        least += op["calls"] * max(flops / rec.peaks["bf16_flops"],
                                   nbytes / rec.peaks["hbm_bytes_per_s"])
        took += op["seconds"]
    return 100.0 * least / took if took > 0 else None
