"""Train step: the share of the step module's device time that no named
scope holds (among it the index sorts of the TPU's scatter lowering), in
percent."""
from bench import scopes


def read(rec):
    return scopes.unscoped_share(rec)
