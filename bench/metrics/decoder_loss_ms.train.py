"""Decoder and loss (``kge.decoder_loss``: negatives, the scored rows'
gathers, DistMult scores, binary cross-entropy, forward and backward): the
step module's device time in that scope, in ms per step of the traced
window."""
from bench import scopes


def read(rec):
    return scopes.ms_per_step(rec, "kge.decoder_loss")
