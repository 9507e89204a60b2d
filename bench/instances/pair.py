"""A pair of sequences to align: a read of ``size`` symbols and a reference
window ``window_extra`` symbols longer, both drawn uniformly from
``alphabet`` symbols, with the configuration's ``scoring``."""


def make(inst: dict, size: int, rng) -> tuple:
    n = size + inst["window_extra"]
    a = inst["alphabet"]
    payload = {"x": rng.integers(0, a, size=size),
               "y": rng.integers(0, a, size=n), **inst["scoring"]}
    return payload, (size, n)
