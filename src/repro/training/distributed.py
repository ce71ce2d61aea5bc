"""Distributed data-parallel training step (paper §2.2, §3.1, Algorithm 1).

The paper's paradigm, mapped to JAX/TPU:

* one trainer per compute unit  →  one partition per slice of the ``data``
  (×``pod``) mesh axis, stacked on the batch leading axis;
* PyTorch DDP + Gloo AllReduce   →  ``shard_map`` + ``jax.lax.pmean`` on
  gradients (lowers to all-reduce over ICI — hardware-native);
* gradient sharing BEFORE the optimizer step (the paper argues this, not
  parameter averaging, preserves mathematical equivalence)  →  grads are
  pmean'd, then one replicated optimizer update.

Two step builders with identical math:

* ``make_spmd_train_step``      — shard_map over a real mesh (pods).
* ``make_simulated_train_step`` — vmap over the trainer axis + mean; runs on
  a single device and is bit-wise the same averaging, used by CPU tests to
  prove distributed == simulated == (for 1 trainer) non-distributed.

In both, the gradient average and the optimizer update run under the named
scope ``kge.optimizer`` (HLO metadata only).
"""
from __future__ import annotations

import functools
from typing import Any, Callable, Dict, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

# plan-carrying batch keys, defined next to the plan layout: the spmd step
# shards them P(data, model) in its in_specs to match the
# BatchShardings.plan transfer placement (per-device plan blocks arrive
# pre-sliced — no resharding on dispatch)
from repro.models.decoders import pairwise_sum
from repro.sharding.embedding import PLAN_BATCH_KEYS
from repro.training.optimizer import Optimizer, apply_updates

PyTree = Any
# loss_fn(params, batch_slice, key) -> (loss, aux)
LossFn = Callable[[PyTree, Dict[str, jax.Array], jax.Array],
                  Tuple[jax.Array, Dict[str, jax.Array]]]


def make_simulated_train_step(
    loss_fn: LossFn, optimizer: Optimizer, *, donate_batch: bool = False,
) -> Callable:
    """Single-device simulation of P trainers: vmap the per-trainer grad,
    average (== AllReduce), one optimizer step.  Batch pytree has a leading
    trainer axis; keys is (P, 2) PRNG keys.

    ``donate_batch`` donates the batch pytree's buffers to the step (the
    exchange arrays — gather plans, inverse maps — are dead after the step,
    so XLA can reuse their memory for the exchange outputs).  Only enable
    it for streamed batches that are never reused (the trainer keeps it off
    for ``FullGraphPipeline``'s resident batch, and on CPU where donation
    is a no-op that warns)."""

    def grad_one(params, batch, key):
        (loss, aux), grads = jax.value_and_grad(
            loss_fn, has_aux=True)(params, batch, key)
        return loss, aux, grads

    @functools.partial(
        jax.jit, donate_argnums=(2,) if donate_batch else ())
    def step(params, opt_state, batch, keys):
        loss, aux, grads = jax.vmap(
            grad_one, in_axes=(None, 0, 0))(params, batch, keys)
        num = loss.shape[0]

        def mean(x):                                   # AllReduce-average
            return pairwise_sum(x) / num

        with jax.named_scope("kge.optimizer"):
            grads = jax.tree_util.tree_map(mean, grads)
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        metrics = {"loss": mean(loss),
                   **{k: mean(v) for k, v in aux.items()}}
        return params, opt_state, metrics

    return step


def derive_opt_state_specs(opt_state: Any, params: Any,
                           param_specs: Any) -> Any:
    """PartitionSpec tree for an optimizer state, derived from its ACTUAL
    structure (``optimizer.init(params)``): any subtree mirroring the
    params structure (adam's mu/nu moments, SGD's momentum buffer) gets
    ``param_specs`` — moments shard exactly like their parameters — and
    every other leaf (the step counter) stays replicated.  ``None``
    subtrees (plain SGD's missing moments) are empty pytrees and stay
    ``None``, so the spec tree always matches the state the optimizer
    really built — no more hardcoded adam-shaped
    ``OptState(step, mu, nu)`` default that trace-errored for SGD.
    """
    p_struct = jax.tree_util.tree_structure(params)

    def params_like(sub) -> bool:
        return jax.tree_util.tree_structure(sub) == p_struct

    return jax.tree_util.tree_map(
        lambda sub: param_specs if params_like(sub) else P(),
        opt_state, is_leaf=params_like)



def make_spmd_train_step(
    loss_fn: LossFn,
    optimizer: Optimizer,
    mesh: Mesh,
    data_axes: Sequence[str] = ("data",),
    replicate_params_axes: Optional[Sequence[str]] = None,
    param_specs: Optional[Any] = None,
    opt_state_specs: Optional[Any] = None,
    model_axis: Optional[str] = None,
    donate_batch: bool = False,
):
    """shard_map train step over a real mesh.

    Batch arrays are sharded on their leading (trainer) axis over
    ``data_axes`` (e.g. ``("pod", "data")`` on the multi-pod mesh); params
    and optimizer state are replicated across those axes.  Inside the shard
    each trainer computes its gradient on its own partition (self-sufficient:
    no neighbor traffic), then ``pmean`` — the AllReduce of Algorithm 1
    line 8 — averages gradients before the shared optimizer step.

    ``param_specs`` (a PartitionSpec pytree mirroring ``params``, e.g.
    ``repro.sharding.kge_param_specs``) opts individual parameters out of
    replication: a model-axis row-sharded entity table
    (``repro.sharding.embedding``) stays sharded through the step — its
    gradients are shard-local by construction (the exchange's backward
    passes each device's replicated cotangent through once, each shard
    scatter-adds only its own rows), so they are pmean'd over
    ``data_axes`` only, like every other leaf, and the optimizer updates
    each row block in place.  The ``loss_fn`` must perform the shard-local
    gather + exchange itself (pass ``model_axis="model"`` into the model's
    ``vertex_input`` path) and the same ``model_axis`` here.

    Optimizer-state specs are derived from the REAL state structure at the
    first call (``derive_opt_state_specs``): moment trees mirroring the
    params shard like the params, scalars stay replicated, absent moments
    (plain/momentum SGD) stay ``None``.  An explicit ``opt_state_specs``
    tree still overrides.

    With ``model_axis`` set, the gather-plan batch keys
    (``PLAN_BATCH_KEYS``) are sharded ``P(data_axes, model_axis)`` — the
    same placement ``BatchShardings`` transfers them with — so each device
    receives its own pre-sliced ``(1, V_b)`` plan block; every other batch
    leaf (and the keys) shards on the leading trainer axis only.

    ``donate_batch`` donates the streamed batch's buffers (gather plans,
    inverse maps, id arrays are dead after the step — XLA reuses them for
    the exchange outputs); keep it off for resident batches that are
    reused across steps (``FullGraphPipeline``).
    """
    data_axes = tuple(data_axes)
    batch_spec = P(data_axes)      # leading trainer axis sharded
    rep_spec = P()                 # params replicated
    p_spec = rep_spec if param_specs is None else param_specs
    model_size = int(mesh.shape.get(model_axis, 1)) if model_axis else 1
    plan_spec = (P(data_axes, model_axis)
                 if model_axis and model_size > 1 else batch_spec)

    def shard_body(params, opt_state, batch, keys):
        # strip the per-shard leading axis of size trainers/shard (==1 when
        # one partition per data slice; >1 when partitions are grouped)
        def one(params, b, k):
            (loss, aux), grads = jax.value_and_grad(
                loss_fn, has_aux=True)(params, b, k)
            return loss, aux, grads

        loss, aux, grads = jax.vmap(one, in_axes=(None, 0, 0))(
            params, batch, keys)
        num = loss.shape[0] * jax.lax.psum(1, data_axes)

        # AllReduce over the trainer axes (and leave other axes alone —
        # model-parallel replicas hold identical grads by construction).
        # The local trainers are summed in pairwise_sum's order, so with up
        # to two data-axis devices (one rounding for the cross-device add,
        # whichever device makes it) the result is bitwise the simulated
        # step's; with more, the all-reduce's own order decides.
        def mean(x):
            return jax.lax.psum(pairwise_sum(x), data_axes) / num

        with jax.named_scope("kge.optimizer"):
            grads = jax.tree_util.tree_map(mean, grads)
        loss = mean(loss)
        aux = jax.tree_util.tree_map(mean, aux)
        with jax.named_scope("kge.optimizer"):
            updates, opt_state = optimizer.update(grads, opt_state, params)
            params = apply_updates(params, updates)
        return params, opt_state, {"loss": loss, **aux}

    from jax.experimental.shard_map import shard_map

    # the shard_map is built lazily at the first call: the opt-state spec
    # tree needs the REAL state structure and the batch spec tree the REAL
    # key set (plan keys present or not), neither known at build time.
    # Cached per (opt-state structure, batch keys) — stable across steps.
    cache: Dict[Any, Callable] = {}

    def build(params, opt_state, batch):
        if opt_state_specs is not None:
            o_spec = opt_state_specs
        else:
            o_spec = derive_opt_state_specs(opt_state, params, p_spec)
        b_spec = {k: plan_spec if k in PLAN_BATCH_KEYS else batch_spec
                  for k in batch}
        sharded = shard_map(
            shard_body, mesh=mesh,
            in_specs=(p_spec, o_spec, b_spec, batch_spec),
            out_specs=(p_spec, o_spec, rep_spec),
            check_rep=False,
        )
        return jax.jit(sharded,
                       donate_argnums=(2,) if donate_batch else ())

    def _lookup(params, opt_state, batch):
        key = (jax.tree_util.tree_structure(opt_state),
               tuple(sorted(batch)))
        fn = cache.get(key)
        if fn is None:
            cache[key] = fn = build(params, opt_state, batch)
        return fn

    def step(params, opt_state, batch, keys):
        fn = _lookup(params, opt_state, batch)
        return fn(params, opt_state, batch, keys)

    def lower(params, opt_state, batch, keys):
        """``jax.stages.Lowered`` for the same jit the step would run —
        the hook ``repro.analysis.programs`` audits the post-SPMD HLO
        through (compile it and read ``.as_text()`` for the per-device
        module)."""
        return _lookup(params, opt_state, batch).lower(
            params, opt_state, batch, keys)

    step.lower = lower
    return step


def replicate_sharding(mesh: Mesh) -> NamedSharding:
    return NamedSharding(mesh, P())


def batch_sharding(mesh: Mesh,
                   data_axes: Sequence[str] = ("data",)) -> NamedSharding:
    return NamedSharding(mesh, P(tuple(data_axes)))


def split_trainer_keys(key: jax.Array, num_trainers: int,
                       step: int) -> jax.Array:
    """Per-trainer, per-step PRNG keys (negative sampling & dropout must
    differ across trainers — each samples its own partition)."""
    base = jax.random.fold_in(key, step)
    return jax.random.split(base, num_trainers)
