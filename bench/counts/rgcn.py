"""Operations the R-GCN + DistMult training step requires, from shapes.

Counted over the real (unpadded) vertices, edges and triplets of each
trainer's computational graph, whatever implements them: per layer, each
vertex state is projected once by each basis (2·B·V·d_in·d) and by the
self-loop weight (2·V·d_in·d); each edge mixes its tail's B projections
with its relation's coefficients (2·B·E·d) and is added into its head
(E·d).  DistMult scores cost 3·d per triplet (two products and a sum per
dimension).  The backward pass costs twice the forward, so a step is three
times the forward.  Elementwise work (activation, dropout, loss, Adam) is
left out; it is a small share and bounded by memory, not by the MXU.
"""
from __future__ import annotations


def forward_flops(vertices: int, edges: int, triplets: int, d_in: int,
                  hidden: int, bases: int, layers: int) -> int:
    flops = 0
    for layer in range(layers):
        di = d_in if layer == 0 else hidden
        flops += 2 * bases * vertices * di * hidden
        flops += 2 * vertices * di * hidden
        flops += 2 * bases * edges * hidden
        flops += edges * hidden
    return flops + 3 * triplets * hidden


def train_step_flops(vertices: int, edges: int, triplets: int, d_in: int,
                     hidden: int, bases: int, layers: int) -> int:
    return 3 * forward_flops(vertices, edges, triplets, d_in, hidden, bases,
                             layers)


def window_flops(train: dict) -> float:
    """Operations of every step the window ran (``counters["train"]``:
    the model's sizes and, for each step, each trainer's real vertices,
    edges and triplets)."""
    return float(sum(
        train_step_flops(v, e, t, train["d_in"], train["hidden"],
                         train["bases"], train["layers"])
        for step in train["steps"] for v, e, t in step))
