"""Open loop, Poisson arrivals: exponential gaps at `rate` requests per
second."""

OPEN = True


def gaps(traffic: dict, n: int, rng):
    return rng.exponential(1.0 / float(traffic["rate"]), size=n)
