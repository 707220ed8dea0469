"""`scale` x product(`num`) / product(`den`); nothing when a term is
missing or the denominator is 0 (nothing happened in the window)."""
from . import terms


def read(window, num, den, scale=1.0):
    n, d = terms.product(window, num), terms.product(window, den)
    if n is None or not d:
        return None
    return scale * n / d
