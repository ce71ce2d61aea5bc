"""Plain reference of the R-GCN + DistMult link-prediction model: weights
and the training step (loss, gradient, Adam).

Straightforward ``jax.numpy`` at the precision it is given: float32 at
``highest`` matmul precision for the reference, bfloat16 throughout for the
control.  It imports nothing of the program.  What it shares with the
program is only the interface: the parameter tree's keys, the batch
arrays a step is fed (the rows to train on, their masks) and the PRNG keys
the step is given.  From those it recomputes the gather, both R-GCN layers
(basis decomposition, mean aggregation over in-edges, self loop, ReLU,
dropout), the negatives, the DistMult scores, the binary cross-entropy,
the gradient, the mean over trainers and the Adam update.

The layer follows Schlichtkrull et al. (2018), Eq. 2 with basis
decomposition, written per vertex: each vertex state is projected once by
each basis, and each edge mixes its tail's projections with its relation's
coefficients.  Negatives follow the paper's constraint-based sampler: one
Bernoulli(0.5) draw per negative picks head or tail, which is replaced by
a uniform draw from the partition's core vertices.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np


# ---------------------------------------------------------------------- #
# weights
# ---------------------------------------------------------------------- #
def _glorot(key, shape):
    fan_in, fan_out = (shape[-2] if len(shape) > 1 else 1), shape[-1]
    return jax.random.normal(key, shape) * np.sqrt(2.0 / (fan_in + fan_out))


@functools.partial(jax.jit, static_argnums=(1, 2, 3, 4, 5, 6))
def init_params(key, num_entities: int, num_relations: int, hidden: int,
                num_bases: int, num_layers: int,
                feature_dim: Optional[int]) -> Dict[str, Any]:
    """The whole parameter tree in one jitted call from ``key``:
    Glorot-normal table, bases, coefficients and self-loop weights, and
    DistMult diagonals N(0, 1/d).  ``num_relations`` counts inverses."""
    keys = iter(jax.random.split(key, 3 * num_layers + 2))
    params: Dict[str, Any] = {}
    if feature_dim is None:
        params["entity_embedding"] = _glorot(next(keys),
                                             (num_entities, hidden))
    layers = []
    for i in range(num_layers):
        d_in = (feature_dim or hidden) if i == 0 else hidden
        layers.append({
            "bases": _glorot(next(keys), (num_bases, d_in, hidden)),
            "coeffs": _glorot(next(keys), (num_relations, num_bases)),
            "self_weight": _glorot(next(keys), (d_in, hidden)),
        })
    params["layers"] = layers
    params["decoder"] = {"rel_diag": jax.random.normal(
        next(keys), (num_relations, hidden)) / np.sqrt(hidden)}
    return params


# ---------------------------------------------------------------------- #
# the training step
# ---------------------------------------------------------------------- #
def _encode(layers, x, src, rel, dst, emask, keys, dropout):
    h = x
    num_v = h.shape[0]
    emask = emask.astype(h.dtype)
    deg = jax.ops.segment_sum(emask, src, num_segments=num_v)
    for i, lp in enumerate(layers):
        proj = jnp.einsum("vd,bdo->vbo", h, lp["bases"])
        msg = jnp.einsum("ebo,eb->eo", proj[dst], lp["coeffs"][rel])
        agg = jax.ops.segment_sum(msg * emask[:, None], src,
                                  num_segments=num_v)
        out = agg / jnp.maximum(deg, 1.0)[:, None] + h @ lp["self_weight"]
        if i < len(layers) - 1:
            out = jax.nn.relu(out)
        keep = jax.random.bernoulli(keys[i], 1 - dropout, out.shape)
        h = jnp.where(keep, out / (1 - dropout), 0.0).astype(x.dtype)
    return h


def _bce(scores, labels, mask):
    per = jnp.maximum(scores, 0) - scores * labels + \
        jnp.log1p(jnp.exp(-jnp.abs(scores)))
    return jnp.sum(per * mask) / jnp.maximum(jnp.sum(mask), 1.0)


def _distmult(h, rel_diag, trip):
    return jnp.sum(h[trip[:, 0]] * rel_diag[trip[:, 1]] * h[trip[:, 2]],
                   axis=-1)


def _vertex_input(params, features, ids, vmask):
    table = params["entity_embedding"] if features is None else features
    return jnp.where(vmask[:, None], table[ids], 0.0)


def triplet_weights(core, num_negatives):
    """Each scored row's weight: a positive edge's core mask, then each
    negative's own edge's (row ``e * s + j`` corrupts edge ``e``)."""
    return jnp.concatenate([core, jnp.repeat(core, num_negatives)])


def fullgraph_loss(params, features, part, key, num_negatives, dropout):
    """One trainer's full-edge-batch loss on its padded partition."""
    k_neg, k_drop = jax.random.split(key)
    layers = params["layers"]
    x = _vertex_input(params, features, part["local_to_global"],
                      part["vertex_mask"])
    h = _encode(layers, x, part["src"], part["rel"], part["dst"],
                part["edge_mask"], jax.random.split(k_drop, len(layers)),
                dropout)
    pos = jnp.stack([part["src"], part["rel"], part["dst"]], axis=1)
    e, s = pos.shape[0], num_negatives
    k_side, k_ent = jax.random.split(k_neg)
    head = jax.random.bernoulli(k_side, 0.5, (e, s))
    repl = jax.random.randint(
        k_ent, (e, s), 0, jnp.maximum(part["num_core_vertices"], 1),
        dtype=jnp.int32)
    rep = jnp.broadcast_to(pos[:, None, :], (e, s, 3))
    neg = jnp.stack([jnp.where(head, repl, rep[..., 0]), rep[..., 1],
                     jnp.where(head, rep[..., 2], repl)], axis=-1)
    trip = jnp.concatenate([pos, neg.reshape(e * s, 3)], axis=0)
    labels = jnp.concatenate([jnp.ones(e), jnp.zeros(e * s)]).astype(
        h.dtype)
    mask = triplet_weights(part["core_edge_mask"].astype(h.dtype), s)
    scores = _distmult(h, params["decoder"]["rel_diag"], trip)
    return _bce(scores, labels, mask)


def minibatch_loss(params, features, batch, key, num_negatives, dropout):
    """One trainer's loss on its padded edge mini-batch (negatives are
    rows of the batch)."""
    layers = params["layers"]
    x = _vertex_input(params, features, batch["gather_global"],
                      batch["vertex_mask"])
    h = _encode(layers, x, batch["comp_src"], batch["comp_rel"],
                batch["comp_dst"], batch["comp_mask"],
                jax.random.split(key, len(layers)), dropout)
    scores = _distmult(h, params["decoder"]["rel_diag"], batch["triplets"])
    return _bce(scores, batch["labels"].astype(h.dtype),
                batch["triplet_mask"].astype(h.dtype))


LOSSES = {"full": fullgraph_loss, "minibatch": minibatch_loss}


def _cast(tree, dtype):
    return jax.tree_util.tree_map(
        lambda x: x.astype(dtype) if jnp.issubdtype(x.dtype, jnp.floating)
        else x, tree)


def replay(params0, features, steps: List[dict], *, mode: str,
           num_negatives: int, dropout: float, lr: float, adam: dict,
           dtype=jnp.float32) -> dict:
    """Run the recorded steps from ``params0``: for each step, every
    trainer's loss and gradient (one trainer at a time, so that it fits),
    their mean, and one Adam update.  Returns each step's mean loss, the
    first step's mean gradient, and the parameters after the last step,
    all on the host.  ``dtype`` is float32 (at ``highest`` precision) for
    the reference and bfloat16 for the control."""
    loss_fn = LOSSES[mode]
    precision = "highest" if dtype == jnp.float32 else "default"

    @jax.jit
    def grad_one(params, feats, part, key):
        with jax.default_matmul_precision(precision):
            return jax.value_and_grad(loss_fn)(
                params, feats, part, key, num_negatives, dropout)

    b1, b2, eps = (float(adam[k]) for k in ("b1", "b2", "eps"))

    @jax.jit
    def adam_step(params, mu, nu, grads, t):
        # moments and parameters are stored in ``dtype``; the scalars stay
        # Python floats, so the bias corrections are exact
        bc1, bc2 = 1 - b1 ** t, 1 - b2 ** t
        mu = jax.tree_util.tree_map(
            lambda m, g: (b1 * m + (1 - b1) * g).astype(dtype), mu, grads)
        nu = jax.tree_util.tree_map(
            lambda v, g: (b2 * v + (1 - b2) * g * g).astype(dtype), nu,
            grads)
        params = jax.tree_util.tree_map(
            lambda p, m, v: (p - lr * (m / bc1) / (
                jnp.sqrt(v / bc2) + eps)).astype(dtype), params, mu, nu)
        return params, mu, nu

    params = _cast(jax.device_put(params0), dtype)
    feats = None if features is None else jnp.asarray(features, dtype)
    mu = jax.tree_util.tree_map(jnp.zeros_like, params)
    nu = jax.tree_util.tree_map(jnp.zeros_like, params)
    losses, grad1 = [], None
    for t, step in enumerate(steps, start=1):
        batch, keys = step["batch"], step["keys"]
        num = keys.shape[0]
        total, gsum = 0.0, None
        for i in range(num):
            part = {k: jnp.asarray(v[i]) for k, v in batch.items()}
            loss, g = grad_one(params, feats, part, jnp.asarray(keys[i]))
            total += float(loss)
            gsum = g if gsum is None else jax.tree_util.tree_map(
                jnp.add, gsum, g)
        grads = jax.tree_util.tree_map(lambda x: x / num, gsum)
        if t == 1:
            grad1 = jax.device_get(grads)
        losses.append(total / num)
        params, mu, nu = adam_step(params, mu, nu, grads,
                                   jnp.asarray(t, jnp.float32))
    return {"losses": losses, "grad1": grad1,
            "params": jax.device_get(params)}

