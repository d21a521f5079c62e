"""Open loop, Gamma(`shape`) gaps at `rate` requests per second: coefficient
of variation 1/sqrt(shape), so a shape under 1 arrives in bursts."""

OPEN = True


def gaps(traffic: dict, n: int, rng):
    shape = float(traffic["shape"])
    return rng.gamma(shape, 1.0 / (shape * float(traffic["rate"])), size=n)
