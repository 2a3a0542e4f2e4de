"""2PS-L with one clustering pass, as ``bench/reference.py`` computes it.

The control scores in bfloat16 instead of the float32 the configuration
states: the step a later change to the scorer would be tempted to take.
"""
from bench import reference

CAPPED = True


def _twopsl(edges, config, traffic, score_dtype, stats=None):
    passes = traffic.get("cli", {}).get("cluster-passes", 1)
    if passes != 1:
        raise ValueError(f"the 2PS-L reference clusters in one pass, the "
                         f"traffic asks for {passes}")
    return reference.twopsl(edges, int(config["k"]), float(config["alpha"]),
                            int(traffic["chunk"]), int(traffic["micro_batch"]),
                            float(traffic["max_vol_factor"]), score_dtype,
                            stats)


def assign(edges, config, traffic, stats=None):
    return _twopsl(edges, config, traffic, "float32", stats)


def control(edges, config, traffic):
    return _twopsl(edges, config, traffic, "bfloat16")
