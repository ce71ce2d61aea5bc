"""Optimizer (``kge.optimizer``: the gradient average over trainers and
Adam): the step module's device time in that scope, in ms per step of
the traced window."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, "kge.optimizer")
