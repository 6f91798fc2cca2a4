"""Experts that had a token in a decode step's routed layer, as a share
of the router's width: ``model_counters.experts_hit`` over
``expert_layer_calls`` times the width, across the window. Where one chip
holds the whole expert set, the bytes its grouped products move follow
this number (an expert with no token is never read), so it tells a
change in the experts' bytes that came from routing or occupancy from
one that came from the kernel. None where the program reports no such
counters or the configuration's adapter states no router width."""

from _lib import counters

from benchmark import model_spec

DECODE = "model_counters"


def read(run):
    c = counters(run)
    width_of = getattr(model_spec.adapter(run["spec"]), "router_width", None)
    if c is None or width_of is None or DECODE not in c[0] \
            or DECODE not in c[1]:
        return None
    calls = c[1][DECODE]["expert_layer_calls"] \
        - c[0][DECODE]["expert_layer_calls"]
    if not calls:
        return None
    hit = c[1][DECODE]["experts_hit"] - c[0][DECODE]["experts_hit"]
    return 100.0 * hit / (calls * width_of(run["spec"]))
