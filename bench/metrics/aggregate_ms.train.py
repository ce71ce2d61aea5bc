"""Aggregation (``kge.aggregate``: the edge mask, the ``segment_sum``s, degree
normalization, self loop and dropout, forward and backward): the step
module's device time in that scope, in ms per step of the traced window."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, "kge.aggregate")
