"""Optimizer, checkpointing, eval ranking, data pipeline, HLO parser,
sharding rules, serving engine."""
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.training import (
    adam, apply_updates, constant_schedule, latest_checkpoint,
    restore_checkpoint, save_checkpoint, sgd, warmup_cosine_schedule,
)


class TestOptimizer:
    def test_adam_converges_quadratic(self):
        opt = adam(0.1)
        params = {"w": jnp.asarray([5.0, -3.0])}
        state = opt.init(params)
        for _ in range(200):
            g = jax.grad(lambda p: jnp.sum(p["w"] ** 2))(params)
            upd, state = opt.update(g, state, params)
            params = apply_updates(params, upd)
        assert float(jnp.abs(params["w"]).max()) < 1e-2

    def test_adam_first_step_magnitude(self):
        """Bias-corrected Adam's first update == lr in each coordinate."""
        opt = adam(0.01)
        params = {"w": jnp.asarray([1.0])}
        state = opt.init(params)
        upd, _ = opt.update({"w": jnp.asarray([123.0])}, state, params)
        assert float(upd["w"][0]) == pytest.approx(-0.01, rel=1e-3)

    def test_grad_clip(self):
        opt = adam(1.0, grad_clip_norm=1.0)
        params = {"w": jnp.zeros(4)}
        state = opt.init(params)
        g = {"w": jnp.full(4, 100.0)}
        upd, _ = opt.update(g, state, params)
        assert np.isfinite(np.asarray(upd["w"])).all()

    def test_sgd_momentum(self):
        opt = sgd(0.1, momentum=0.9)
        params = {"w": jnp.asarray([1.0])}
        state = opt.init(params)
        upd1, state = opt.update({"w": jnp.asarray([1.0])}, state, params)
        upd2, state = opt.update({"w": jnp.asarray([1.0])}, state, params)
        assert float(upd2["w"][0]) == pytest.approx(-0.19, rel=1e-4)

    def test_schedules(self):
        s = warmup_cosine_schedule(1.0, 10, 100)
        assert float(s(jnp.asarray(5))) == pytest.approx(0.5)
        assert float(s(jnp.asarray(10))) == pytest.approx(1.0, rel=1e-3)
        assert float(s(jnp.asarray(100))) == pytest.approx(0.0, abs=1e-6)
        assert float(constant_schedule(0.3)(jnp.asarray(7))) == \
            pytest.approx(0.3)

    def test_bf16_state_dtype(self):
        opt = adam(0.01, state_dtype=jnp.bfloat16)
        params = {"w": jnp.zeros(4, jnp.float32)}
        state = opt.init(params)
        assert state.mu["w"].dtype == jnp.bfloat16


class TestCheckpoint:
    def test_roundtrip(self, tmp_path):
        tree = {"a": jnp.arange(6).reshape(2, 3),
                "b": [jnp.ones(4), {"c": jnp.zeros((2, 2))}]}
        path = save_checkpoint(str(tmp_path), 7, tree, {"note": "x"})
        assert latest_checkpoint(str(tmp_path)) == path
        step, restored = restore_checkpoint(path, tree)
        assert step == 7
        for x, y in zip(jax.tree_util.tree_leaves(tree),
                        jax.tree_util.tree_leaves(restored)):
            np.testing.assert_array_equal(np.asarray(x), np.asarray(y))

    def test_gc_keeps_last(self, tmp_path):
        tree = {"a": jnp.zeros(2)}
        for s in range(5):
            save_checkpoint(str(tmp_path), s, tree, keep=2)
        files = [f for f in os.listdir(tmp_path) if f.endswith(".npz")]
        assert len(files) == 2

    def test_shape_mismatch_raises(self, tmp_path):
        path = save_checkpoint(str(tmp_path), 0, {"a": jnp.zeros(3)})
        with pytest.raises(ValueError):
            restore_checkpoint(path, {"a": jnp.zeros(4)})

    def test_gc_keep_zero_keeps_everything(self, tmp_path):
        """keep<=0 is the documented KEEP-ALL contract (the old
        ``ckpts[:-keep] if keep`` only kept-all for exactly 0; a negative
        keep would have deleted the NEWEST checkpoints)."""
        tree = {"a": jnp.zeros(2)}
        for keep in (0, -1):
            for s in range(4):
                save_checkpoint(str(tmp_path), s, tree, keep=keep)
            files = [f for f in os.listdir(tmp_path)
                     if f.endswith(".npz")]
            assert len(files) == 4

    def test_gc_removes_orphaned_manifests(self, tmp_path):
        """A .json manifest whose .npz payload is gone (crashed save,
        out-of-band cleanup) is pruned on the next save — even with
        keep=0 — so it can never shadow a real checkpoint."""
        tree = {"a": jnp.zeros(2)}
        save_checkpoint(str(tmp_path), 1, tree)
        path2 = save_checkpoint(str(tmp_path), 2, tree)
        os.remove(path2)                       # orphan ckpt_00000002.json
        save_checkpoint(str(tmp_path), 3, tree, keep=0)
        names = sorted(os.listdir(tmp_path))
        assert "ckpt_00000002.json" not in names
        assert {"ckpt_00000001.npz", "ckpt_00000001.json",
                "ckpt_00000003.npz", "ckpt_00000003.json"} <= set(names)

    def test_metadata_roundtrip(self, tmp_path):
        from repro.training.checkpoint import read_metadata
        path = save_checkpoint(str(tmp_path), 5, {"a": jnp.zeros(2)},
                               metadata={"epoch": 5, "key": [1, 2]})
        step, meta = read_metadata(path)
        assert step == 5
        assert meta == {"epoch": 5, "key": [1, 2]}


class TestRankingEval:
    def test_known_ranks(self):
        """Hand-crafted embeddings with known ranking."""
        from repro.eval import ranking_metrics
        # entity i has embedding e_i = onehot(i); rel diag all ones;
        # head 0 scores highest against candidate 0
        n, d = 8, 8
        emb = np.eye(n, d, dtype=np.float32)
        table = np.ones((1, d), np.float32)
        tests = np.array([[0, 0, 0]])          # (s=0, r=0, t=0): rank 1
        m = ranking_metrics(emb, {"rel_diag": table}, tests, {})
        assert m["mrr"] == pytest.approx(1.0)
        assert m["hits@1"] == 1.0

    def test_filter_removes_known_positives(self):
        from repro.eval import ranking_metrics
        n, d = 4, 4
        emb = np.eye(n, d, dtype=np.float32) + 0.5
        table = np.ones((1, d), np.float32)
        # without filtering, entity 1 ties/beats others for head 0
        tests = np.array([[0, 0, 2]])
        fidx = {(0, 0): {1, 2}}     # 1 is a known positive -> filtered
        m = ranking_metrics(emb, {"rel_diag": table}, tests, fidx)
        m_nof = ranking_metrics(emb, {"rel_diag": table}, tests, {})
        assert m["mrr"] >= m_nof["mrr"]

    def test_candidate_mode(self):
        from repro.eval import ranking_metrics
        rng = np.random.default_rng(0)
        n, d = 50, 8
        emb = rng.normal(size=(n, d)).astype(np.float32)
        table = np.ones((2, d), np.float32)
        tests = np.array([[0, 0, 1], [2, 1, 3]])
        cands = rng.integers(0, n, (2, 10))
        m = ranking_metrics(emb, {"rel_diag": table}, tests, {}, candidates=cands)
        assert 0 < m["mrr"] <= 1.0


class TestData:
    def test_fb15k_format_loader(self, tmp_path):
        from repro.data import load_fb15k_format
        for split, rows in (("train", ["a\tr1\tb", "b\tr2\tc"]),
                            ("valid", ["a\tr1\tc"]),
                            ("test", ["c\tr2\ta"])):
            (tmp_path / f"{split}.txt").write_text("\n".join(rows) + "\n")
        splits = load_fb15k_format(str(tmp_path))
        assert splits["train"].num_edges == 2
        assert splits["train"].num_entities == 3
        assert splits["test"].num_relations == 2

    def test_synthetic_shapes(self):
        from repro.data import synthetic_citation2, synthetic_fb15k
        s1 = synthetic_fb15k(scale=0.01)
        assert s1["train"].features is None
        s2 = synthetic_citation2(scale=0.0003)
        assert s2["train"].features.shape[1] == 128

    def test_token_stream_deterministic(self):
        from repro.data import TokenStream
        a = next(iter(TokenStream(100, 2, 8, seed=1)))
        b = next(iter(TokenStream(100, 2, 8, seed=1)))
        np.testing.assert_array_equal(a["tokens"], b["tokens"])
        assert a["tokens"].shape == (2, 8)


class TestHLOAnalysis:
    HLO = """
HloModule test

%while_body.1 (p: (s32[], f32[8,8])) -> (s32[], f32[8,8]) {
  %p = (s32[], f32[8,8]{1,0}) parameter(0)
  %x = f32[8,8]{1,0} get-tuple-element(%p), index=1
  %ar = f32[8,8]{1,0} all-reduce(%x), replica_groups={}, to_apply=%add
  %d = f32[8,8]{1,0} dot(%ar, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

ENTRY %main (a: f32[16,4]) -> f32[16,4] {
  %a = f32[16,4]{1,0} parameter(0)
  %ag = f32[16,64]{1,0} all-gather(%a), dimensions={1}
  %w = (s32[], f32[8,8]{1,0}) while(%t), condition=%cond.1, body=%while_body.1
}
"""

    def test_collective_loop_scaling(self):
        from repro.sharding.hlo_analysis import collective_stats
        s1 = collective_stats(self.HLO, loop_trip_count=1)
        s10 = collective_stats(self.HLO, loop_trip_count=10)
        # all-reduce inside body: 8*8*4 bytes * 2 (ring) * trip
        assert s1["all-reduce"]["bytes"] == pytest.approx(512)
        assert s10["all-reduce"]["bytes"] == pytest.approx(5120)
        # all-gather in entry: not scaled
        assert s1["all-gather"]["bytes"] == \
            s10["all-gather"]["bytes"] == 16 * 64 * 4

    def test_dot_flops_loop_scaling(self):
        from repro.sharding.hlo_analysis import analyze_hlo
        r1 = analyze_hlo(self.HLO, loop_trip_count=1)
        r5 = analyze_hlo(self.HLO, loop_trip_count=5)
        # dot: 2 * 64 * 8 flops, inside loop
        assert r1["flops"] == pytest.approx(2 * 64 * 8)
        assert r5["flops"] == pytest.approx(5 * 2 * 64 * 8)


class TestShardingRules:
    def test_param_specs_divisible(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import spec_for_param
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             devices=jax.devices()[:1])
        # divisible dims get sharded (axis size 1 divides everything)
        s = spec_for_param(("layers", "attn", "w_q"), (64, 128), mesh)
        assert s == P("data", "model")
        # unknown names replicate
        assert spec_for_param(("foo",), (64,), mesh) == P()

    def test_indivisible_falls_back(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import spec_for_param
        if len(jax.devices()) < 1:
            pytest.skip("no devices")
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             devices=jax.devices()[:1])
        # shape smaller than rule arity replicates
        assert spec_for_param(("w_q",), (7,), mesh) == P()

    def test_moe_expert_rule(self):
        import jax
        from jax.sharding import PartitionSpec as P
        from repro.sharding.rules import spec_for_param
        mesh = jax.make_mesh((1, 1), ("data", "model"),
                             devices=jax.devices()[:1])
        s = spec_for_param(("groups", "0", "moe", "w_in"), (4, 16, 32),
                           mesh)
        assert s == P("model", "data", None)


class TestServing:
    def test_engine_greedy_decode(self):
        from repro.configs import get_arch
        from repro.nn import init_params
        from repro.serving import Request, ServeEngine
        cfg = get_arch("gemma-2b").reduced()
        params = init_params(jax.random.PRNGKey(0), cfg,
                             dtype=jnp.float32)
        eng = ServeEngine(cfg, params, slots=2, max_seq=32)
        reqs = [Request(i, np.array([1 + i, 5, 9], np.int32),
                        max_new_tokens=4) for i in range(3)]
        done = eng.run(reqs)
        assert all(r.done for r in done)
        assert all(len(r.output) == 4 for r in done)
        assert all(0 <= t < cfg.vocab_size
                   for r in done for t in r.output)

    def test_kge_server_topk(self):
        from repro.serving import KGEServer
        rng = np.random.default_rng(0)
        emb = rng.normal(size=(40, 8)).astype(np.float32)
        srv = KGEServer(emb, {"rel_diag": np.ones((2, 8), np.float32)})
        top = srv.topk_tails(np.array([0, 1]), np.array([0, 1]), k=5)
        assert top.shape == (2, 5)
        # top-1 must be the argmax of the exact scores
        want = np.argmax(emb @ emb[:2].T, axis=0)
        assert (top[:, 0] == want).all()

    def test_kge_server_every_decoder(self):
        """The serving path carries every registered decoder: top-1 must be
        the argmax of that decoder's exact XLA scores."""
        from repro.models.decoders import (
            init_decoder_params, registered_decoders,
            score_against_candidates,
        )
        from repro.serving import KGEServer
        rng = np.random.default_rng(1)
        emb = rng.normal(size=(50, 8)).astype(np.float32)
        heads, rels = np.array([0, 3, 7]), np.array([0, 1, 2])
        for name in registered_decoders():
            p = init_decoder_params(jax.random.PRNGKey(0), name, 3, 8)
            srv = KGEServer(emb, p, decoder=name)
            top = srv.topk_tails(heads, rels, k=4)
            want = score_against_candidates(
                p, name, jnp.asarray(emb[heads]), jnp.asarray(rels),
                jnp.asarray(emb))
            assert (top[:, 0] == np.argmax(np.asarray(want), axis=1)).all(), \
                name


class TestHLONesting:
    NESTED = """
HloModule nested

%inner_cond.1 (p: (s32[], f32[4,4])) -> pred[] {
  %p = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%p), index=0
  %n = s32[] constant(8)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%inner_body.1 (p: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %p = (s32[], f32[4,4]{1,0}) parameter(0)
  %x = f32[4,4]{1,0} get-tuple-element(%p), index=1
  %d = f32[4,4]{1,0} dot(%x, %x), lhs_contracting_dims={1}, rhs_contracting_dims={0}
}

%outer_cond.1 (q: (s32[], f32[4,4])) -> pred[] {
  %q = (s32[], f32[4,4]{1,0}) parameter(0)
  %i = s32[] get-tuple-element(%q), index=0
  %n = s32[] constant(3)
  ROOT %lt = pred[] compare(%i, %n), direction=LT
}

%outer_body.1 (q: (s32[], f32[4,4])) -> (s32[], f32[4,4]) {
  %q = (s32[], f32[4,4]{1,0}) parameter(0)
  %w = (s32[], f32[4,4]{1,0}) while(%q), condition=%inner_cond.1, body=%inner_body.1
}

ENTRY %main (a: f32[4,4]) -> f32[4,4] {
  %a = f32[4,4]{1,0} parameter(0)
  %w = (s32[], f32[4,4]{1,0}) while(%t), condition=%outer_cond.1, body=%outer_body.1
}
"""

    def test_nested_trip_product(self):
        """Inner-loop dots scale by outer×inner trip (3×8=24)."""
        from repro.sharding.hlo_analysis import analyze_hlo
        r = analyze_hlo(self.NESTED)
        # dot: 2 * 16 * 4 = 128 flops, × 24
        assert r["flops"] == pytest.approx(128 * 24)

    def test_trip_from_condition_constant(self):
        """Auto-detected trips override the fallback default."""
        from repro.sharding.hlo_analysis import analyze_hlo
        r_default = analyze_hlo(self.NESTED, loop_trip_count=999)
        assert r_default["flops"] == pytest.approx(128 * 24)

    def test_tuple_collective_with_comments(self):
        """Tuple all-reduce types contain /*index=N*/ comments; bytes must
        still parse (regression for the v2 parser bug)."""
        from repro.sharding.hlo_analysis import collective_stats
        hlo = ("ENTRY %m (a: f32[4]) -> f32[4] {\n"
               "%all-reduce = (f32[2,2]{1,0}, f32[8]{0}, f32[2]{0}, "
               "f32[4]{0}, f32[2]{0}, /*index=5*/f32[2]{0}) "
               "all-reduce(%a, %b)\n}\n")
        st = collective_stats(hlo)
        assert st["all-reduce"]["count"] == 1
        assert st["all-reduce"]["bytes"] == 2 * (4 + 8 + 2 + 4 + 2 + 2) * 4


class TestServingMoreArchs:
    @pytest.mark.parametrize("arch", ["qwen2-vl-7b", "deepseek-v2-lite-16b",
                                      "rwkv6-3b"])
    def test_engine_all_families(self, arch):
        from repro.configs import get_arch
        from repro.nn import init_params
        from repro.serving import Request, ServeEngine
        cfg = get_arch(arch).reduced()
        params = init_params(jax.random.PRNGKey(0), cfg, dtype=jnp.float32)
        eng = ServeEngine(cfg, params, slots=2, max_seq=24)
        done = eng.run([Request(0, np.array([1, 2], np.int32),
                                max_new_tokens=3)])
        assert done[0].done and len(done[0].output) == 3


class TestLaunchHelpers:
    def test_chip_peaks_only_for_known_kinds(self):
        from repro.launch.mesh import chip_peaks
        assert chip_peaks("TPU v5 lite")["hbm_bw"] == 819e9
        for kind in ("TPU v4", "TPU v6 lite", "cpu"):
            with pytest.raises(ValueError, match="no peak table"):
                chip_peaks(kind)

    def test_compile_cache_honours_the_environment(self, monkeypatch,
                                                   tmp_path):
        from repro.launch.compile_cache import enable_compile_cache
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert enable_compile_cache() == str(tmp_path)
        assert jax.config.jax_compilation_cache_dir == before

    def test_compile_cache_defaults_to_one_checkout_dir(self, monkeypatch):
        from repro.launch.compile_cache import (
            CHECKOUT_CACHE_DIR, enable_compile_cache,
        )
        root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
        before = jax.config.jax_compilation_cache_dir
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        try:
            got = enable_compile_cache()
            assert got == os.path.join(root, ".jax_cache")
            assert got == str(CHECKOUT_CACHE_DIR) == enable_compile_cache()
            assert jax.config.jax_compilation_cache_dir == got
        finally:
            jax.config.update("jax_compilation_cache_dir", before)
        with open(os.path.join(root, ".gitignore")) as f:
            assert ".jax_cache/" in f.read().split()
