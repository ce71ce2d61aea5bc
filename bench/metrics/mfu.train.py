"""Train step: the model operations every step of the window required
(``bench/counts/rgcn.py``, real vertices and edges only) over the window
and the chips' bf16 peak, in percent."""
from bench.counts import rgcn


def read(rec):
    c, peaks = rec["counters"], rec["peaks"]
    if peaks is None or "train" not in c:
        return None
    return 100.0 * rgcn.window_flops(c["train"]) / (
        c["window_s"] * rec["chips"] * peaks["flops_bf16_per_s"])
