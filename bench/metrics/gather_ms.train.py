"""Embedding gather (``kge.gather``: the entity rows of each trainer's
vertices and their table-gradient scatter): the step module's device
time in that scope, in ms per step of the traced window."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, "kge.gather")
