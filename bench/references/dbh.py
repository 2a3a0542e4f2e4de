"""DBH, the hash of the lower-degree endpoint, as ``bench/reference.py``
computes it.

The control counts degrees in 8 bits (saturating at 255) instead of 32.
"""
from bench import reference

CAPPED = False


def assign(edges, config, traffic, stats=None):
    return reference.dbh(edges, int(config["k"]))


def control(edges, config, traffic):
    return reference.dbh(edges, int(config["k"]), saturate_at=255)
