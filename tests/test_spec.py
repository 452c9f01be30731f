"""Speculative decoding (engine/spec.py): exactness + engine wiring.

The safety property is that speculation changes WHEN tokens are computed,
never WHICH distribution they come from: greedy streams must be
bit-identical to the non-speculative decoder (any cache corruption or
verification bug shows up within a few tokens), and the stochastic
verifier's accept/resample rule must reproduce the processed sampling
distribution exactly (checked against analytic probabilities on a fixed
logit row). Reference behavior being replaced: the strictly one-token-
per-model-call HF generate loop (GUI_RAFT_LLM_SourceCode/
tutoring_server.py:21-29).
"""

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from distributed_lms_raft_llm_tpu.engine.generate import decode, prefill
from distributed_lms_raft_llm_tpu.engine.sampling import (
    SamplingParams,
    sample_step,
    seen_mask_from_ids,
)
from distributed_lms_raft_llm_tpu.engine.spec import (
    build_drafts,
    decode_spec,
    verify_window,
)
from distributed_lms_raft_llm_tpu.models import gpt2, llama, registry


def _prompt(cfg, b=3, t=8, seed=2, ragged=True):
    ids = np.asarray(
        jax.random.randint(jax.random.key(seed), (b, t), 1, cfg.vocab_size),
        np.int32,
    )
    mask = np.ones((b, t), bool)
    if ragged:
        mask[1, :3] = False
    return jnp.asarray(ids), jnp.asarray(mask)


def _run_both(cfg, family, sampling, *, eos=0, spec_tokens=4, seed=1,
              b=3, t=8, quant_kv=False):
    import dataclasses

    if quant_kv:
        cfg = dataclasses.replace(cfg, quant_kv=True)
    params = family.init_params(jax.random.key(0), cfg)
    ids, mask = _prompt(cfg, b=b, t=t)
    rng = jax.random.key(seed)
    st = prefill(params, cfg, ids, mask, rng, sampling, eos, 0, model=family)
    ref, _ = decode(params, st, cfg, sampling, eos, 0, model=family)
    st2 = prefill(params, cfg, ids, mask, rng, sampling, eos, 0, model=family)
    spec, _ = decode_spec(
        params, st2, ids, cfg, sampling, eos, 0, model=family,
        spec_tokens=spec_tokens,
    )
    return jax.device_get(ref), jax.device_get(spec)


class TestGreedyBitEquality:
    """temperature=0 makes every sampling decision deterministic, so the
    speculative and sequential decoders must emit IDENTICAL streams —
    the sharpest possible check of window verification, ragged cache
    writes, seen-mask evolution, and budget/EOS bookkeeping."""

    def test_gpt2_matches(self):
        ref, spec = _run_both(
            gpt2.GPT2Config.tiny(), registry.GPT2_FAMILY,
            SamplingParams.greedy(max_new_tokens=16),
        )
        np.testing.assert_array_equal(ref.tokens, spec.tokens)
        np.testing.assert_array_equal(ref.lengths, spec.lengths)

    def test_gpt2_with_repetition_penalty(self):
        # Penalty 1.2 exercises the seen-mask path inside the verifier:
        # a token accepted mid-window must penalize the rest of the window.
        sp = SamplingParams(temperature=0.0, top_k=50, top_p=1.0,
                            repetition_penalty=1.2, max_new_tokens=20)
        ref, spec = _run_both(gpt2.GPT2Config.tiny(), registry.GPT2_FAMILY, sp)
        np.testing.assert_array_equal(ref.tokens, spec.tokens)
        np.testing.assert_array_equal(ref.lengths, spec.lengths)

    def test_gpt2_int8_kv(self):
        ref, spec = _run_both(
            gpt2.GPT2Config.tiny(), registry.GPT2_FAMILY,
            SamplingParams.greedy(max_new_tokens=16), quant_kv=True,
        )
        np.testing.assert_array_equal(ref.tokens, spec.tokens)

    def test_llama_matches(self):
        ref, spec = _run_both(
            llama.LlamaConfig.tiny(), registry.LLAMA_FAMILY,
            SamplingParams.greedy(max_new_tokens=16),
        )
        np.testing.assert_array_equal(ref.tokens, spec.tokens)
        np.testing.assert_array_equal(ref.lengths, spec.lengths)

    def test_eos_stops_rows(self):
        # Force frequent EOS by making it a likely token: pick the model's
        # actual greedy argmax after a few steps as the eos id.
        cfg = gpt2.GPT2Config.tiny()
        fam = registry.GPT2_FAMILY
        sp = SamplingParams.greedy(max_new_tokens=16)
        params = fam.init_params(jax.random.key(0), cfg)
        ids, mask = _prompt(cfg)
        rng = jax.random.key(1)
        st = prefill(params, cfg, ids, mask, rng, sp, 0, 0, model=fam)
        probe, _ = decode(params, st, cfg, sp, 0, 0, model=fam)
        eos = int(np.asarray(probe.tokens)[0, 4])  # a token greedy WILL hit
        ref, spec = _run_both(cfg, fam, sp, eos=eos)
        np.testing.assert_array_equal(ref.tokens, spec.tokens)
        np.testing.assert_array_equal(ref.lengths, spec.lengths)
        assert int(spec.lengths[0]) < 16  # actually stopped early

    def test_spec_width_spans_budget_boundary(self):
        # max_new not divisible by the window width: the budget clamp
        # drops the tail of the last window.
        for k in (1, 3, 5):
            ref, spec = _run_both(
                gpt2.GPT2Config.tiny(), registry.GPT2_FAMILY,
                SamplingParams.greedy(max_new_tokens=7), spec_tokens=k,
            )
            np.testing.assert_array_equal(ref.tokens, spec.tokens)


class TestRaggedMultiTokenCacheWrite:
    """The per-row scatter write (models/*.forward, offset.ndim==1, T>1)
    must agree with the scalar dynamic_update_slice path when every row
    sits at the same offset."""

    @pytest.mark.parametrize("family,cfg", [
        (registry.GPT2_FAMILY, gpt2.GPT2Config.tiny()),
        (registry.LLAMA_FAMILY, llama.LlamaConfig.tiny()),
    ])
    @pytest.mark.parametrize("quant_kv", [False, True])
    def test_matches_scalar_path(self, family, cfg, quant_kv):
        import dataclasses

        cfg = dataclasses.replace(cfg, quant_kv=quant_kv)
        params = family.init_params(jax.random.key(0), cfg)
        b, t0, tw = 2, 6, 4
        prompt = jax.random.randint(jax.random.key(3), (b, t0), 1,
                                    cfg.vocab_size)
        window = jax.random.randint(jax.random.key(4), (b, tw), 1,
                                    cfg.vocab_size)
        cache = family.init_cache(cfg, b, t0 + tw, dtype=cfg.dtype)
        _, cache = family.forward(params, cfg, prompt, cache=cache)

        lg_s, c_s = family.forward(params, cfg, window, cache=cache)
        ragged = cache._replace(
            length=jnp.full((b,), t0, jnp.int32)
        )
        lg_r, c_r = family.forward(params, cfg, window, cache=ragged)

        np.testing.assert_allclose(np.asarray(lg_s), np.asarray(lg_r),
                                   rtol=2e-5, atol=2e-5)
        np.testing.assert_array_equal(
            np.asarray(c_s.k, np.float32), np.asarray(c_r.k, np.float32)
        )
        np.testing.assert_array_equal(
            np.asarray(c_s.v, np.float32), np.asarray(c_r.v, np.float32)
        )

    def test_rows_at_different_offsets(self):
        # Row r's window lands at its own offset; other rows' slots are
        # untouched. Directly validates cross-row isolation of the scatter.
        cfg = gpt2.GPT2Config.tiny()
        fam = registry.GPT2_FAMILY
        params = fam.init_params(jax.random.key(0), cfg)
        b, tw, width = 2, 3, 12
        offs = jnp.asarray([2, 5], jnp.int32)
        window = jax.random.randint(jax.random.key(5), (b, tw), 1,
                                    cfg.vocab_size)
        cache = fam.init_cache(cfg, b, width, dtype=cfg.dtype)
        marker = cache._replace(
            k=jnp.full_like(cache.k, 7.0), v=jnp.full_like(cache.v, 7.0),
            length=offs,
        )
        _, out = fam.forward(params, cfg, window, cache=marker)
        k = np.asarray(out.k, np.float32)
        for r, o in enumerate([2, 5]):
            touched = np.any(k[:, r] != 7.0, axis=(0, 1, 3))  # [width] per slot
            assert touched[o : o + tw].all()
            assert not touched[:o].any() and not touched[o + tw :].any()


class TestVerifierDistribution:
    """The accept/resample rule must reproduce the processed sampling
    distribution exactly. With a point-mass draft q=δ(d), speculative
    sampling accepts with p(d) and otherwise resamples from p restricted
    to V∖{d} — whose mixture is p itself. Checked empirically against
    sample_step's analytic distribution on a fixed logit row."""

    def _empirical(self, logits_row, draft, sampling, trials=4000):
        b = trials
        drafts = jnp.full((b, 1), draft, jnp.int32)
        logits = jnp.broadcast_to(
            logits_row, (b, 2, logits_row.shape[-1])
        )
        seen = jnp.zeros((b, logits_row.shape[-1]), jnp.bool_)
        emitted, valid, _, _ = verify_window(
            jax.random.key(9), logits, drafts, seen,
            jnp.ones((b,), jnp.bool_), sampling, eos_id=-1, pad_id=-1,
        )
        emitted = np.asarray(emitted)
        valid = np.asarray(valid)
        assert valid[:, 0].all()
        return emitted[:, 0]

    def test_first_position_matches_sample_step(self):
        v = 64
        rng = np.random.default_rng(0)
        logits_row = jnp.asarray(rng.normal(0, 2.0, (v,)), jnp.float32)
        sampling = SamplingParams(temperature=0.7, top_k=16, top_p=0.9,
                                  repetition_penalty=1.0, max_new_tokens=4)
        draft = int(jnp.argsort(logits_row)[-2])  # a plausible draft

        got = self._empirical(logits_row, draft, sampling)

        # Analytic processed distribution via sample_step on a huge batch
        # of fresh keys (its own correctness is golden-tested vs HF).
        b = 4000
        seen = jnp.zeros((b, v), jnp.bool_)
        ref = sample_step(
            jax.random.key(123),
            jnp.broadcast_to(logits_row, (b, v)), seen, sampling,
        )
        ref = np.asarray(ref)

        # Compare frequency tables over the nucleus support.
        support = sorted(set(ref.tolist()) | set(got.tolist()))
        f_got = np.array([(got == s).mean() for s in support])
        f_ref = np.array([(ref == s).mean() for s in support])
        # 4000 trials: binomial std ≤ ~0.008; allow 5 sigma.
        np.testing.assert_allclose(f_got, f_ref, atol=0.04)

    def test_rejected_draft_never_reemitted_when_p_zero(self):
        # A draft outside the top-k support has p=0 under the processed
        # distribution: it must never be emitted.
        v = 64
        rng = np.random.default_rng(1)
        logits_row = jnp.asarray(rng.normal(0, 2.0, (v,)), jnp.float32)
        sampling = SamplingParams(temperature=0.7, top_k=8, top_p=1.0,
                                  repetition_penalty=1.0, max_new_tokens=4)
        draft = int(jnp.argsort(logits_row)[0])  # the WORST token
        got = self._empirical(logits_row, draft, sampling, trials=1000)
        assert (got != draft).all()


class TestDrafts:
    def test_bigram_preferred_over_unigram(self):
        # transcript: ... 5 9 ... 7 9 ... [7 9] → bigram (7,9) matches at
        # the second 9; proposal continues from there, not from the first.
        tr = jnp.asarray([[5, 9, 1, 2, 7, 9, 3, 4, 7, 9, 0, 0]], jnp.int32)
        # The current bigram is slots 8-9; match_valid (as decode_spec
        # builds it) anchors only earlier slots.
        valid = jnp.asarray([[True] * 9 + [False] * 3])
        d = build_drafts(tr, valid, jnp.asarray([7]), jnp.asarray([9]), 3)
        np.testing.assert_array_equal(np.asarray(d), [[3, 4, 7]])

    def test_unigram_fallback_and_recency(self):
        tr = jnp.asarray([[9, 1, 2, 9, 3, 4, 0, 0]], jnp.int32)
        valid = jnp.asarray([[True] * 6 + [False, False]])
        # prev token 8 matches nowhere → unigram on 9, most recent (idx 3).
        d = build_drafts(tr, valid, jnp.asarray([8]), jnp.asarray([9]), 2)
        np.testing.assert_array_equal(np.asarray(d), [[3, 4]])

    def test_no_match_repeats_last(self):
        tr = jnp.asarray([[1, 2, 3, 4]], jnp.int32)
        valid = jnp.ones((1, 4), jnp.bool_)
        d = build_drafts(tr, valid, jnp.asarray([6]), jnp.asarray([7]), 2)
        np.testing.assert_array_equal(np.asarray(d), [[7, 7]])


class TestEngineWiring:
    def test_engine_spec_roundtrip(self):
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="tiny",
            sampling=SamplingParams.reference_defaults(max_new_tokens=12),
            length_buckets=(16,), batch_buckets=(1, 2), spec_tokens=4,
        ))
        answers = eng.answer_batch(["what is a raft quorum?"])
        assert len(answers) == 1 and isinstance(answers[0], str)

    def test_engine_spec_composes_with_tp(self):
        # The verify window's ragged multi-token scatter must partition
        # over a tp-sharded cache (heads axis untouched by the indices).
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="tiny",
            sampling=SamplingParams.reference_defaults(max_new_tokens=12),
            length_buckets=(16,), batch_buckets=(1, 2), spec_tokens=4,
            tp=2, quant="int8", kv_quant=True,
        ))
        answers = eng.answer_batch(["explain quorums", "what is a log?"])
        assert len(answers) == 2
        assert all(isinstance(a, str) for a in answers)

    def test_engine_reports_tokens_per_window(self):
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="tiny",
            sampling=SamplingParams.greedy(max_new_tokens=12),
            length_buckets=(16,), batch_buckets=(1,), spec_tokens=4,
        ))
        assert eng.last_spec_tokens_per_window is None
        eng.answer_batch(["the the the the"])
        tpw = eng.last_spec_tokens_per_window
        # Prefill token excluded: the ceiling is exactly spec_tokens + 1.
        assert tpw is not None and 0.0 < tpw <= 5.0

    def test_warmup_caps_bucket_inside_position_budget(self):
        # tiny has max_position_embeddings=64: an uncapped warmup at
        # length_buckets[0]=48 with max_new=16 + k=4 would oversubscribe
        # the position table (48+16+4-1=67 > 64) and trip decode_spec's
        # new budget validation on a shape real traffic can never reach
        # (encode_prompts caps at _max_prompt_len). warmup must cap too.
        from distributed_lms_raft_llm_tpu.engine import (
            EngineConfig,
            TutoringEngine,
        )

        eng = TutoringEngine(EngineConfig(
            model="tiny",
            sampling=SamplingParams.greedy(max_new_tokens=16),
            length_buckets=(48,), batch_buckets=(1,), spec_tokens=4,
        ))
        eng.warmup(batch=1)  # must not raise
        answers = eng.answer_batch(["a question after warmup"])
        assert len(answers) == 1

    def test_decode_spec_rejects_oversubscribed_position_budget(self):
        # Direct decode_spec callers get a loud error, not silently
        # clamped (wrong) position embeddings (ADVICE round 5): prefill's
        # own guard passes (t + max_new == mpe) but the spec window's
        # k-1 overhang does not fit.
        from distributed_lms_raft_llm_tpu.engine import generate as gen_lib
        from distributed_lms_raft_llm_tpu.engine.spec import decode_spec
        from distributed_lms_raft_llm_tpu.models import registry

        family, cfg = registry.resolve("tiny", jnp.float32)
        params = family.init_params(jax.random.PRNGKey(0), cfg)
        t = 8
        sampling = SamplingParams.greedy(
            max_new_tokens=cfg.max_position_embeddings - t
        )
        ids = jnp.ones((1, t), jnp.int32)
        mask = jnp.ones((1, t), bool)
        state = gen_lib.prefill(params, cfg, ids, mask,
                                jax.random.PRNGKey(1), sampling,
                                eos_id=0, pad_id=0, model=family)
        with pytest.raises(ValueError, match="max_position_embeddings"):
            decode_spec(params, state, ids, cfg, sampling, eos_id=0,
                        pad_id=0, model=family, spec_tokens=4)
