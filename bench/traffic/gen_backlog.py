"""Closed backlog: at every tick the load generator tops the queue up to
`queue_per_slot` x slots requests."""

import math

OPEN = False


def depth(traffic: dict, slots: int) -> int:
    return int(math.ceil(float(traffic["queue_per_slot"]) * slots))
