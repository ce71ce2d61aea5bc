"""Message passing (``kge.message``: each edge's ``h[dst]`` gather, the basis
projection and the relation mix, forward and backward): the step module's
device time in that scope, in ms per step of the traced window."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, "kge.message")
