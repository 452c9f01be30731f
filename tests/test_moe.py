"""Mixture-of-Experts GPT-2 + expert parallelism (models/moe.py).

The reference serves one dense architecture (GUI_RAFT_LLM_SourceCode/
tutoring_server.py:10-12); MoE is a beyond-reference capability, so the
correctness bar is internal: the static dispatch/combine einsum layer must
match a brute-force per-token reference exactly, ep-sharded execution must
match single-device execution, and the full serving engine must drive the
family through the standard generate path (the trunk IS gpt2.forward, so
cache/decode/speculation come along for free — asserted here too).
"""

import dataclasses

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.engine.sampling import SamplingParams
from distributed_lms_raft_llm_tpu.models import moe, registry
from distributed_lms_raft_llm_tpu.parallel import make_mesh, partition


def _layer0(params):
    return jax.tree.map(lambda a: a[0], params["blocks"]["moe"])


def _brute_force(x, mp, cfg):
    """Per-token loop with float64 math: top-k, renormalize, weighted sum."""
    x = np.asarray(x, np.float64)
    wr = np.asarray(mp["wr"], np.float64)
    logits = x @ wr
    p = np.exp(logits - logits.max(-1, keepdims=True))
    p /= p.sum(-1, keepdims=True)

    def gelu(v):
        return 0.5 * v * (
            1 + np.tanh(np.sqrt(2 / np.pi) * (v + 0.044715 * v**3))
        )

    out = np.zeros_like(x)
    for s in range(x.shape[0]):
        order = np.argsort(-p[s])[: cfg.experts_per_token]
        w = p[s][order]
        w = w / w.sum()
        for wi, e in zip(w, order):
            mid = gelu(
                x[s] @ np.asarray(mp["wi"][e], np.float64)
                + np.asarray(mp["bi"][e], np.float64)
            )
            out[s] += wi * (
                mid @ np.asarray(mp["wo"][e], np.float64)
                + np.asarray(mp["bo"][e], np.float64)
            )
    return out


class TestMoELayer:
    @pytest.mark.parametrize("k", [1, 2])
    def test_matches_brute_force_without_drops(self, k):
        cfg = moe.GPT2MoEConfig.tiny(
            capacity_factor=100.0, experts_per_token=k
        )
        params = moe.init_params(jax.random.key(0), cfg)
        mp = _layer0(params)
        h = jax.random.normal(jax.random.key(1), (2, 5, cfg.hidden_size),
                              jnp.float32)
        y = np.asarray(moe.moe_mlp(h, mp, cfg)).reshape(-1, cfg.hidden_size)
        ref = _brute_force(
            np.asarray(h).reshape(-1, cfg.hidden_size), mp, cfg
        )
        np.testing.assert_allclose(y, ref, atol=2e-4)

    def test_capacity_drops_route_to_zero(self):
        # C=1: at most E slots across the whole batch carry tokens; every
        # dropped token contributes exactly 0 (residual passthrough).
        cfg = moe.GPT2MoEConfig.tiny(capacity_factor=1e-9)
        params = moe.init_params(jax.random.key(0), cfg)
        mp = _layer0(params)
        h = jax.random.normal(jax.random.key(2), (4, 8, cfg.hidden_size),
                              jnp.float32)
        assert moe.capacity(cfg, 32) == 1
        y = np.asarray(moe.moe_mlp(h, mp, cfg)).reshape(-1, cfg.hidden_size)
        nonzero = np.sum(np.any(np.abs(y) > 0, axis=1))
        assert 0 < nonzero <= cfg.num_experts

    def test_slot_priority_is_first_choice_first(self):
        # Crafted collision at capacity 1: token A prefers E0 then E1,
        # token B prefers E1 then E0. Slot-major priority means BOTH get
        # their FIRST choice (all first picks outrank any second pick) and
        # both second picks are dropped — so each token's output is its
        # renormalized-first-choice expert alone. An inverted priority
        # would hand each token its SECOND choice instead, which this
        # assertion distinguishes.
        cfg = moe.GPT2MoEConfig.tiny(capacity_factor=1e-9)  # C = 1
        params = moe.init_params(jax.random.key(0), cfg)
        mp = dict(_layer0(params))
        d, e = cfg.hidden_size, cfg.num_experts
        wr = np.full((d, e), -30.0, np.float32)
        wr[0, 0], wr[0, 1] = 3.0, 2.0   # token A = e_0: E0 > E1
        wr[1, 1], wr[1, 0] = 3.0, 2.0   # token B = e_1: E1 > E0
        mp["wr"] = jnp.asarray(wr)
        h = np.zeros((1, 2, d), np.float32)
        h[0, 0, 0] = 1.0  # token A
        h[0, 1, 1] = 1.0  # token B
        assert moe.capacity(cfg, 2) == 1
        y = np.asarray(moe.moe_mlp(jnp.asarray(h), mp, cfg))[0]

        def expert(x, idx):
            wi = np.asarray(mp["wi"][idx], np.float64)
            bi = np.asarray(mp["bi"][idx], np.float64)
            wo = np.asarray(mp["wo"][idx], np.float64)
            bo = np.asarray(mp["bo"][idx], np.float64)
            v = x @ wi + bi
            g = 0.5 * v * (1 + np.tanh(np.sqrt(2 / np.pi)
                                       * (v + 0.044715 * v**3)))
            return g @ wo + bo

        # Renormalized first-choice weight: softmax(3,2) over the top-2.
        w1 = float(np.exp(3.0) / (np.exp(3.0) + np.exp(2.0)))
        exp_a = w1 * expert(np.asarray(h[0, 0], np.float64), 0)
        exp_b = w1 * expert(np.asarray(h[0, 1], np.float64), 1)
        np.testing.assert_allclose(y[0], exp_a, atol=2e-4)
        np.testing.assert_allclose(y[1], exp_b, atol=2e-4)

    def test_load_balance_loss_positive_and_bounded(self):
        cfg = moe.GPT2MoEConfig.tiny()
        params = moe.init_params(jax.random.key(0), cfg)
        h = jax.random.normal(jax.random.key(4), (2, 8, cfg.hidden_size),
                              jnp.float32)
        loss = float(moe.load_balance_loss(params, cfg, h, layer=0))
        # Perfectly balanced -> 1.0; worst case -> E. Must lie in [1, E].
        assert 0.9 <= loss <= cfg.num_experts + 1e-3


class TestExpertParallel:
    def test_ep_sharded_matches_single_device(self):
        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        params = moe.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(5), (2, 12), 0,
                                 cfg.vocab_size)
        dense_logits, _ = moe.forward(params, cfg, ids)

        mesh = make_mesh({"ep": 4, "dp": -1})
        assert mesh.shape["ep"] == 4
        rules = partition.RULES_FOR["gpt2_moe"]
        sharded = partition.shard_tree(params, mesh, rules)
        with mesh:
            ep_logits, _ = jax.jit(
                lambda p, i: moe.forward(p, cfg, i)
            )(sharded, ids)
        np.testing.assert_allclose(
            np.asarray(dense_logits), np.asarray(ep_logits),
            rtol=2e-5, atol=2e-5,
        )

    def test_ep_composes_with_tp(self):
        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        params = moe.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(6), (2, 8), 0,
                                 cfg.vocab_size)
        dense_logits, _ = moe.forward(params, cfg, ids)
        mesh = make_mesh({"ep": 2, "tp": 2, "dp": -1})
        sharded = partition.shard_tree(
            params, mesh, partition.RULES_FOR["gpt2_moe"]
        )
        with mesh:
            out, _ = jax.jit(lambda p, i: moe.forward(p, cfg, i))(
                sharded, ids
            )
        np.testing.assert_allclose(
            np.asarray(dense_logits), np.asarray(out), rtol=2e-5, atol=2e-5
        )


class TestTraining:
    def test_ep_sharded_train_step_loss_decreases(self):
        from distributed_lms_raft_llm_tpu.train import (
            TrainConfig,
            make_sharded_train_step,
        )

        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        mesh = make_mesh({"ep": 2, "tp": 2, "dp": -1})
        step, state, batch_shardings = make_sharded_train_step(
            mesh, cfg,
            TrainConfig(learning_rate=1e-2, warmup_steps=1, remat=True),
            jax.random.key(0),
        )
        seq = np.tile(np.arange(16, dtype=np.int32), (8, 2))
        batch = {
            "input_ids": jax.device_put(seq, batch_shardings["input_ids"]),
            "loss_mask": jax.device_put(
                np.ones_like(seq, np.float32), batch_shardings["loss_mask"]
            ),
        }
        losses, balances = [], []
        with mesh:
            for _ in range(8):
                state, metrics = step(state, batch)
                losses.append(float(metrics["loss"]))
                balances.append(float(metrics["moe_balance"]))
        assert np.isfinite(losses).all()
        assert losses[-1] < losses[0] * 0.8, losses
        # The Switch aux metric lives in [1, E] (1 = perfectly balanced).
        assert all(0.9 <= b <= cfg.num_experts + 1e-3 for b in balances)
        # Expert stacks actually sharded over ep.
        wi_shard = state["params"]["blocks"]["moe"]["wi"].sharding
        assert "ep" in (wi_shard.spec[1],), wi_shard.spec

    def test_forward_with_aux_matches_forward_logits(self):
        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        params = moe.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(8), (2, 10), 0,
                                 cfg.vocab_size)
        ref, _ = moe.forward(params, cfg, ids)
        got, aux = moe.forward_with_aux(params, cfg, ids)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)
        assert 0.9 <= float(aux) <= cfg.num_experts

    def test_train_export_serves_through_engine(self, tmp_path):
        # The full loop: ep-sharded train step -> native-layout export ->
        # TutoringEngine loads it via the standard checkpoint path.
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )
        from distributed_lms_raft_llm_tpu.train import (
            TrainConfig,
            make_sharded_train_step,
        )
        from distributed_lms_raft_llm_tpu.train.checkpoint import (
            export_model,
        )

        cfg = moe.GPT2MoEConfig.tiny()
        mesh = make_mesh({"ep": 2, "dp": -1})
        step, state, shardings = make_sharded_train_step(
            mesh, cfg, TrainConfig(warmup_steps=1), jax.random.key(0)
        )
        seq = np.tile(np.arange(16, dtype=np.int32), (4, 2))
        batch = {
            "input_ids": jax.device_put(seq, shardings["input_ids"]),
            "loss_mask": jax.device_put(
                np.ones_like(seq, np.float32), shardings["loss_mask"]
            ),
        }
        with mesh:
            state, _ = step(state, batch)
        path = str(tmp_path / "moe.safetensors")
        export_model(path, state)

        eng = TutoringEngine(EngineConfig(
            model="moe-tiny", checkpoint=path,
            sampling=SamplingParams.reference_defaults(max_new_tokens=8),
            length_buckets=(16,), batch_buckets=(1,),
        ))
        # Trained weights actually loaded (not random init): compare one
        # leaf against the exported state.
        got = np.asarray(eng.params["blocks"]["moe"]["wr"], np.float32)
        want = np.asarray(
            jax.device_get(state["params"]["blocks"]["moe"]["wr"]),
            np.float32,
        )
        np.testing.assert_allclose(got, want, rtol=1e-2, atol=1e-2)
        assert isinstance(eng.answer_batch(["q"])[0], str)

    def test_moe_refuses_pp(self):
        import pytest as _pytest

        from distributed_lms_raft_llm_tpu.train import (
            TrainConfig,
            make_sharded_train_step,
        )

        cfg = moe.GPT2MoEConfig.tiny()
        with _pytest.raises(ValueError, match="pp and MoE"):
            make_sharded_train_step(
                make_mesh({"pp": 2, "dp": -1}), cfg,
                TrainConfig(warmup_steps=1), jax.random.key(0),
            )

    def test_ring_attention_composes_with_moe_and_aux(self):
        # sp x ep x dp: the ring-routed full-sequence forward and its aux
        # channel must match the dense single-device forward exactly.
        import dataclasses

        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        params = moe.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(5), (4, 16), 0,
                                 cfg.vocab_size)
        ref, aux_ref = moe.forward_with_aux(params, cfg, ids)
        mesh = make_mesh({"sp": 2, "ep": 2, "dp": -1})
        ring_cfg = dataclasses.replace(cfg, ring_mesh=mesh)
        sharded = partition.shard_tree(
            params, mesh, partition.RULES_FOR["gpt2_moe"]
        )
        with mesh:
            got, aux = jax.jit(
                lambda p, i: moe.forward_with_aux(p, ring_cfg, i)
            )(sharded, ids)
        np.testing.assert_allclose(np.asarray(ref), np.asarray(got),
                                   rtol=2e-5, atol=2e-5)
        assert abs(float(aux) - float(aux_ref)) < 1e-4


class TestServing:
    def test_engine_serves_moe_with_ep(self):
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="moe-tiny",
            sampling=SamplingParams.reference_defaults(max_new_tokens=10),
            length_buckets=(16,), batch_buckets=(1, 2), ep=4,
        ))
        assert eng.mesh.shape["ep"] == 4
        answers = eng.answer_batch(["what is a quorum?", "explain logs"])
        assert len(answers) == 2 and all(isinstance(a, str) for a in answers)

    def test_moe_composes_with_speculative_decoding(self):
        # The trunk is gpt2.forward, so the spec verify window (ragged
        # multi-token cache writes) must run unchanged: greedy streams
        # bit-equal with and without speculation. capacity_factor >= E
        # disables dropping, making the layer per-token independent —
        # with drops enabled a token's output depends on what else is in
        # the forward (batch-context dependence inherent to Switch-style
        # capacity), so the window and step forwards may legitimately
        # disagree (documented in models/moe.py).
        from distributed_lms_raft_llm_tpu.engine.generate import (
            decode,
            prefill,
        )
        from distributed_lms_raft_llm_tpu.engine.spec import decode_spec

        cfg = moe.GPT2MoEConfig.tiny(capacity_factor=4.0)
        fam = registry.MOE_FAMILY
        params = fam.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(7), (2, 8), 1,
                                 cfg.vocab_size)
        mask = jnp.ones((2, 8), jnp.bool_)
        sp = SamplingParams.greedy(max_new_tokens=12)
        st = prefill(params, cfg, ids, mask, jax.random.key(1), sp, 0, 0,
                     model=fam)
        ref, _ = decode(params, st, cfg, sp, 0, 0, model=fam)
        st2 = prefill(params, cfg, ids, mask, jax.random.key(1), sp, 0, 0,
                      model=fam)
        spec, _ = decode_spec(params, st2, ids, cfg, sp, 0, 0, model=fam,
                              spec_tokens=3)
        np.testing.assert_array_equal(
            np.asarray(ref.tokens), np.asarray(spec.tokens)
        )

    def test_paged_engine_serves_moe(self):
        # Continuous batching over the MoE family: per-slot ragged decode
        # + the dispatch einsums under one chunked step program, with the
        # expert stacks ep-sharded.
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            PagedEngine,
        )

        eng = PagedEngine(EngineConfig(
            model="moe-tiny",
            sampling=SamplingParams.reference_defaults(max_new_tokens=8),
            length_buckets=(16,), batch_buckets=(1, 2), ep=4,
        ), slots=2, chunk=4)
        assert eng.mesh.shape["ep"] == 4
        rids = [eng.submit("what is a log?"), eng.submit("quorum?")]
        out = eng.drain()
        assert all(isinstance(out[r], str) for r in rids)

    def test_engine_rejects_ep_for_dense_family(self):
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        with pytest.raises(ValueError, match="requires an MoE family"):
            TutoringEngine(EngineConfig(model="tiny", ep=2))

    def test_engine_rejects_spec_with_dropping_moe(self):
        # Default capacity_factor (1.25) drops tokens, which breaks the
        # spec verifier's exactness contract — must fail loudly.
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        with pytest.raises(ValueError, match="capacity_factor"):
            TutoringEngine(EngineConfig(model="moe-tiny", spec_tokens=4))

    def test_quantized_trunk_serves(self):
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="moe-tiny",
            sampling=SamplingParams.reference_defaults(max_new_tokens=8),
            length_buckets=(16,), batch_buckets=(1,),
            quant="int8", kv_quant=True,
        ))
        assert eng.answer_batch(["hello"])[0] is not None

    def test_int8_experts_stay_close_to_dense(self):
        # Weight-only int8 on the expert stacks (and trunk): the forward
        # must track the full-precision one closely — same bar as the
        # dense-model quant tests (top-1 agreement on most positions).
        from distributed_lms_raft_llm_tpu.models import quant

        cfg = moe.GPT2MoEConfig.tiny(dtype=jnp.float32,
                                     param_dtype=jnp.float32)
        params = moe.init_params(jax.random.key(0), cfg)
        ids = jax.random.randint(jax.random.key(9), (2, 12), 0,
                                 cfg.vocab_size)
        ref, _ = moe.forward(params, cfg, ids)
        qparams = quant.quantize_params(params, "gpt2_moe")
        assert isinstance(qparams["blocks"]["moe"]["wi"], dict)  # quantized
        got, _ = moe.forward(qparams, cfg, ids)
        ref_np, got_np = np.asarray(ref), np.asarray(got)
        agree = np.mean(
            np.argmax(ref_np, axis=-1) == np.argmax(got_np, axis=-1)
        )
        assert agree >= 0.9, agree
        # And the int8 expert stacks still shard over ep.
        mesh = make_mesh({"ep": 4, "dp": -1})
        sharded = partition.shard_tree(
            qparams, mesh, partition.RULES_FOR["gpt2_moe"]
        )
        with mesh:
            ep_logits, _ = jax.jit(lambda p, i: moe.forward(p, cfg, i))(
                sharded, ids
            )
        np.testing.assert_allclose(got_np, np.asarray(ep_logits),
                                   rtol=2e-5, atol=2e-5)
