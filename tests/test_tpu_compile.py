"""Compile the main-path Pallas kernels for a described TPU v5e chip.

Nothing runs: the TPU compiler that ships with jax compiles each kernel
for one chip of a described (not attached) ``v5e:2x2`` topology, at the
paper's FB15k-237 widths (N = 14,592 padded entities, d = 75, V = 4,096
gathered rows, k = 10).  This catches what interpret mode cannot: block
shapes the (8, 128) tiling rule refuses, loops the TPU lowering cannot
carry, and kernels that need more VMEM than the chip gives.  Each case
goes through the kernel's default ``interpret=None`` / ``use_kernel=None``
and checks that the compiled program holds the kernel, so a TPU lowering
that fell back to interpret mode or to XLA fails here.

The topology is described inside a module fixture, never at import time:
only one process may load the TPU library, and every test worker imports
this file.
"""
from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest

N_PAD, D, V, K = 14_592, 75, 4_096, 10
B = 256           # query rows (two 128-row tiles)
E = 32_768        # message-passing edges (a tile multiple)


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    from jax.sharding import SingleDeviceSharding

    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one; keep the cache out of it
    was_on = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was_on)
    compilation_cache.reset_cache()


def _case(name):
    from repro.kernels import ops, rgcn_message, sharded_gather
    from repro.kernels.kge_score import kge_score
    from repro.kernels.topk import topk_scores

    f32, i32 = jnp.float32, jnp.int32
    cases = {
        "kge_score_bilinear": (
            lambda *a: kge_score(*a, epilogue="bilinear"),
            [((B, D), f32), ((N_PAD, D), f32), ((B, N_PAD), f32),
             ((B, 1), f32), ((1, N_PAD), f32)]),
        "kge_score_neg_l2": (
            lambda *a: kge_score(*a, epilogue="neg_l2"),
            [((B, D), f32), ((N_PAD, D), f32), ((B, N_PAD), f32),
             ((B, 1), f32), ((1, N_PAD), f32)]),
        "fused_gather": (
            sharded_gather.fused_gather,
            [((N_PAD, D), f32), ((V,), i32), ((V,), jnp.bool_)]),
        "fused_dequant_gather": (
            sharded_gather.fused_dequant_gather,
            [((N_PAD, D), jnp.int8), ((N_PAD,), f32), ((V,), i32),
             ((V,), jnp.bool_)]),
        "scatter_add_onehot": (
            lambda g, f, o: sharded_gather.scatter_add_onehot(g, f, o, N_PAD),
            [((V, D), f32), ((V,), i32), ((V,), jnp.bool_)]),
        "topk_scores": (
            lambda s: topk_scores(s, K, num_cols=N_PAD, c_block=2_048),
            [((128, 16_384), f32)]),
        "topk_padded_dispatch": (
            lambda s: ops.topk_padded(s, K), [((8, N_PAD), f32)]),
        "fused_sharded_gather_dispatch": (
            ops.fused_sharded_gather,
            [((2, N_PAD // 2, D), f32), ((2, V), i32), ((2, V), jnp.bool_)]),
        "dequant_sharded_gather_dispatch": (
            ops.dequant_sharded_gather,
            [((2, N_PAD // 2, D), jnp.int8), ((2, N_PAD // 2), f32),
             ((2, V), i32), ((2, V), jnp.bool_)]),
        "basis_message": (
            rgcn_message.basis_message,
            [((E, D), f32), ((E, 2), f32), ((2, D, D), f32),
             ((E,), jnp.bool_)]),
        "segment_sum_onehot": (
            lambda m, s, k: rgcn_message.segment_sum_onehot(m, s, k, N_PAD),
            [((E, D), f32), ((E,), i32), ((E,), jnp.bool_)]),
    }
    return cases[name]


CASES = ["kge_score_bilinear", "kge_score_neg_l2", "fused_gather",
         "fused_dequant_gather", "scatter_add_onehot", "topk_scores",
         "topk_padded_dispatch", "fused_sharded_gather_dispatch",
         "dequant_sharded_gather_dispatch", "basis_message",
         "segment_sum_onehot"]


@pytest.mark.parametrize("name", CASES)
def test_kernel_compiles_for_v5e(one_chip, name):
    fn, shapes = _case(name)
    args = [jax.ShapeDtypeStruct(s, dt, sharding=one_chip)
            for s, dt in shapes]
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text(), \
        f"{name}: the TPU program holds no Pallas kernel"
