"""TutoringEngine: the plain bucketed generator the tests hold the served
engine's answers equal to. It serves nothing.

`engine.paged.PagedEngine` behind `engine.batcher.PagedQueue` is what the
server, the shipped configurations, the simulator and the benchmark run.
This class stays because it shares nothing of admission or of the decode
scan with it: prompts are tokenized and **left-padded into static
buckets** (length and batch both bucketed to powers of two), and a
request batch runs to completion as one jitted prefill + while_loop
decode program (`engine.generate`), sampling included. Greedy answers of
the two must be bit-equal (tests/test_paged.py, test_megastep.py,
test_fused_prefill.py and the families' tests), so a fault in the served
scan shows against a generator too simple to share it.

`EngineConfig` below is the configuration of both.
"""

from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import convert, quant, registry
from ..parallel import mesh as mesh_lib
from ..parallel import partition
from ..utils import tokenizer as tok_lib
from ..utils.compilation import enable_compilation_cache
from ..utils.guards import intended_transfer
from .generate import GenerateResult, decode, pick_bucket, prefill
from .sampling import SamplingParams
from .spans import named_partial

log = logging.getLogger(__name__)


@dataclasses.dataclass
class EngineConfig:
    model: str = "gpt2"  # any models/registry.py preset (gpt2* | llama*)
    checkpoint: Optional[str] = None  # .safetensors path (HF layout)
    vocab_path: Optional[str] = None   # GPT-2 vocab.json
    merges_path: Optional[str] = None  # GPT-2 merges.txt
    tokenizer_json: Optional[str] = None  # HF tokenizer.json (Llama et al.)
    sampling: SamplingParams = dataclasses.field(
        default_factory=SamplingParams.reference_defaults
    )
    length_buckets: Tuple[int, ...] = (32, 64, 128, 256)
    batch_buckets: Tuple[int, ...] = (1, 2, 4, 8)
    tp: int = 1  # tensor-parallel ways; dp absorbs remaining devices
    # Expert-parallel ways (MoE presets: gpt2-moe / moe-tiny): the expert
    # stacks shard over the `ep` mesh axis (parallel/partition.py
    # MOE_RULES). Composes with tp x dp; 1 for dense models.
    ep: int = 1
    # Weight-only int8 ("int8") halves the parameter bytes the decode loop
    # streams per step (models/quant.py). None = full-precision (bf16)
    # weights. Composes with tp>1 (the
    # partition rules shard the quantized {q, s} leaf pairs).
    quant: Optional[str] = None
    # int8 KV cache (per-slot scales, models/common.quantize_kv): halves
    # the attention bytes per decode step. Orthogonal to `quant`.
    kv_quant: bool = False
    # Decode-segment count: the KV cache grows to each segment's high-water
    # mark instead of being final-size from step one, so attention streams
    # only slots that can be valid yet (generate.decode). None = auto from
    # the batch size (4 small / 8 large); 1 = single full-size while_loop.
    decode_segments: Optional[int] = None
    # Speculative decoding (engine/draft.py kernels): propose this many
    # prompt-lookup draft tokens per step and verify them in one forward
    # with exact rejection sampling — several tokens per model call,
    # identical output distribution. 0 = off. PagedEngine generalizes
    # its chunked step to per-slot verify windows
    # (engine/paged._spec_step_program — slot lengths advance raggedly by
    # per-row accepted counts); the reference swaps decode for
    # engine/spec.decode_spec (supersedes decode_segments; the spec cache
    # grows once to its high-water width). Wins where per-step fixed
    # costs dominate: low batch, or a batch running below capacity.
    spec_tokens: int = 0
    # Spec draft source: "prompt_lookup" (most-recent n-gram continuation,
    # engine/draft.build_drafts — the right bet for greedy streams) or
    # "ngram" (per-slot modal-continuation n-gram table,
    # build_drafts_ngram — higher acceptance on stochastic temperature>0
    # streams, where recency stops predicting what the sampler emits).
    # "ngram" is a PagedEngine feature (the table reads the SlotState
    # transcript); TutoringEngine rejects it rather than silently
    # drafting differently than configured.
    draft_source: str = "prompt_lookup"
    # Background bulk-scoring tenant (engine/scoring.py): when True,
    # warmup compiles the score program over its full (batch bucket x
    # length bucket) domain — `expected_from_inventory` then asserts the
    # set exactly, so the first instructor bulk job pays zero live XLA
    # compiles. score() works either way; off just means on-demand
    # compilation (a bench/offline convenience, never the serving path).
    scoring: bool = False
    dtype: Any = jnp.bfloat16
    # Serving stores weights in bf16: halves the HBM read per decode step
    # versus f32 (the decode loop is memory-bound — every step streams all
    # parameters from HBM). Golden tests override to f32 for bit-accuracy.
    param_dtype: Any = jnp.bfloat16
    seed: int = 0


class TutoringEngine:
    def __init__(self, config: EngineConfig, devices: Optional[Sequence] = None):
        enable_compilation_cache()
        self.config = config
        if config.spec_tokens > 0 and config.draft_source != "prompt_lookup":
            raise ValueError(
                f"draft_source {config.draft_source!r} is a paged-engine "
                "feature (the n-gram table reads the per-slot SlotState "
                "transcript); TutoringEngine drafts via prompt_lookup only"
            )
        self.family, self.cfg = registry.resolve(
            config.model, config.dtype, config.param_dtype
        )
        if config.ep > 1 and not self.family.expert_parallel:
            raise ValueError(
                f"ep={config.ep} requires an MoE family whose expert axis "
                f"shards over ep; the {self.family.name!r} family of "
                f"{config.model!r} has none "
                f"— the ep devices would silently replicate (shrinking "
                f"dp) instead of helping"
            )
        if config.tp > 1 and self.family.latent_cache:
            raise ValueError(
                f"tp={config.tp}: {config.model!r} caches a latent with "
                f"no heads axis (models/mla.py); there is nothing for tp "
                f"to shard"
            )
        if self.family.recurrent_state and (
                config.spec_tokens > 0 or config.tp > 1):
            raise ValueError(
                f"{config.model!r} carries a recurrent state in its cache "
                f"(models/mamba2.py): spec_tokens={config.spec_tokens} "
                f"needs a verify window that rolls the state back past a "
                f"rejected draft, and tp={config.tp} the state's heads "
                f"sharded beside the mixer's projections; neither is built"
            )
        if (
            config.spec_tokens > 0
            and self.family.name == "gpt2_moe"
            and self.cfg.capacity_factor < self.cfg.num_experts
        ):
            raise ValueError(
                "spec_tokens with an MoE model requires capacity_factor >= "
                "num_experts (no token dropping): capacity drops make a "
                "token's output depend on its forward-pass companions, so "
                "the speculative verify window would sample from different "
                "distributions than step decode (models/moe.py caveat)"
            )
        self.mesh = mesh_lib.make_mesh(
            {"tp": config.tp, "ep": config.ep, "dp": -1},
            devices=devices,
        )
        if config.kv_quant:
            self.cfg = dataclasses.replace(self.cfg, quant_kv=True)
        self.tokenizer = tok_lib.load_gpt2_tokenizer(
            config.vocab_path, config.merges_path, config.tokenizer_json
        )
        if self.family.name == "llama" and config.checkpoint and not (
            config.tokenizer_json
        ):
            raise ValueError(
                "a Llama checkpoint needs its own tokenizer: pass "
                "tokenizer_json (HF tokenizer.json) — GPT-2 BPE/byte ids "
                "would silently map to wrong embedding rows"
            )
        if self.tokenizer.vocab_size > self.cfg.vocab_size:
            raise ValueError(
                f"tokenizer vocab {self.tokenizer.vocab_size} exceeds model "
                f"vocab {self.cfg.vocab_size}"
            )
        # Generation must leave room for at least one prompt token in the
        # position table (see gpt2.forward precondition on silent clamping).
        if config.sampling.max_new_tokens >= self.cfg.max_position_embeddings:
            raise ValueError(
                f"max_new_tokens {config.sampling.max_new_tokens} must be < "
                f"max_position_embeddings {self.cfg.max_position_embeddings} "
                f"for model {config.model!r}"
            )
        self._rng = jax.random.key(config.seed)

        t0 = time.monotonic()
        if config.checkpoint:
            sd = convert.load_safetensors(config.checkpoint)
            params = self.family.params_from_hf(sd, self.cfg)
        else:
            log.warning("no checkpoint configured — randomly initialized %s",
                        config.model)
            params = self.family.init_params(jax.random.key(config.seed), self.cfg)
        if config.quant:
            if config.quant != "int8":
                raise ValueError(f"unsupported quant mode {config.quant!r}")
            # Composes with tp: the partition rules cover the quantized
            # {q, s} leaf pairs (parallel/partition.py) — q shards like the
            # dense leaf, scales follow their out-channel axis.
            params = quant.quantize_params(params, self.family.name)
        rules = partition.RULES_FOR[self.family.name]
        self.params = partition.shard_tree(params, self.mesh, rules)
        log.info("params ready in %.1fs (mesh %s)", time.monotonic() - t0,
                 dict(zip(self.mesh.axis_names, self.mesh.devices.shape)))

        # Two jitted programs per input shape (prefill, decode): decode is
        # handed prefill's state donated, so the KV cache buffers are
        # reused in place across the handoff. jit itself specializes/caches
        # per (batch bucket, length bucket).
        statics = dict(
            cfg=self.cfg,
            sampling=self.config.sampling,
            eos_id=self.tokenizer.eos_id,
            pad_id=self.tokenizer.pad_id,
            model=self.family,
        )
        self._prefill = jax.jit(named_partial(prefill, **statics))
        if config.spec_tokens > 0:
            from .spec import decode_spec

            self._decode = jax.jit(
                named_partial(decode_spec, spec_tokens=config.spec_tokens,
                              **statics),
                donate_argnums=(1,),
            )
        else:
            self._decode = jax.jit(
                named_partial(decode, segments=config.decode_segments,
                              **statics),
                donate_argnums=(1,),
            )
        # Mean emitted tokens per verify window of the last generate
        # (1.0 + acceptance; None until a spec generate ran).
        self.last_spec_tokens_per_window: Optional[float] = None

    def _max_prompt_len(self) -> int:
        # Spec mode keeps its verify windows inside the position table:
        # the widest window ends k-1 positions past the last budgeted token.
        extra = max(0, self.config.spec_tokens - 1)
        return min(
            max(self.config.length_buckets),
            self.cfg.max_position_embeddings
            - self.config.sampling.max_new_tokens - extra,
        )

    def encode_prompts(self, prompts: Sequence[str]) -> Tuple[np.ndarray, np.ndarray, int]:
        """Tokenize + left-pad into (ids, mask, bucket).

        len(prompts) must not exceed the largest batch bucket (answer_batch
        chunks larger groups).
        """
        if len(prompts) > max(self.config.batch_buckets):
            raise ValueError(
                f"{len(prompts)} prompts exceed the largest batch bucket "
                f"{max(self.config.batch_buckets)}"
            )
        limit = self._max_prompt_len()
        token_lists = []
        for p in prompts:
            toks = self.tokenizer.encode(p)[-limit:]  # keep the prompt tail
            token_lists.append(toks if toks else [self.tokenizer.pad_id])
        longest = max(len(t) for t in token_lists)
        bucket = pick_bucket(longest, self.config.length_buckets)
        bucket = min(bucket, limit)
        nbatch = pick_bucket(len(prompts), self.config.batch_buckets)
        ids = np.full((nbatch, bucket), self.tokenizer.pad_id, np.int32)
        mask = np.zeros((nbatch, bucket), bool)
        for i, toks in enumerate(token_lists):
            ids[i, bucket - len(toks):] = toks
            mask[i, bucket - len(toks):] = True
        # Filler rows (batch bucketing) keep one valid token to stay well-formed.
        for i in range(len(prompts), nbatch):
            mask[i, -1] = True
        return ids, mask, bucket

    # ----------------------------------------------------------------- API

    def warmup(self, batch: int = 8, bucket: Optional[int] = None) -> float:
        """Pre-compile the hot program; returns compile seconds."""
        # Cap like encode_prompts does: live traffic never exceeds
        # _max_prompt_len(), and an uncapped warmup bucket would trip
        # decode_spec's position-budget validation (spec mode with a small
        # position table) on a shape real requests can't reach.
        bucket = min(bucket or self.config.length_buckets[0],
                     self._max_prompt_len())
        t0 = time.monotonic()
        ids = np.zeros((batch, bucket), np.int32)
        mask = np.ones((batch, bucket), bool)
        self.generate_ids(ids, mask)
        return time.monotonic() - t0

    def generate_ids(
        self,
        ids: np.ndarray,
        mask: np.ndarray,
        real_rows: Optional[int] = None,
    ) -> GenerateResult:
        """Generate for a pre-bucketed id batch; the result is on the
        host."""
        self._rng, rng = jax.random.split(self._rng)
        with self.mesh:
            state = self._prefill(self.params, input_ids=jnp.asarray(ids),
                                  prompt_mask=jnp.asarray(mask), rng=rng)
            # The final state is returned (and dropped) so the donated input
            # state's same-shaped buffers (out/seen/rng/flags) alias into the
            # outputs; the cache intentionally grows instead — see decode().
            if self.config.spec_tokens > 0:
                result, fin = self._decode(self.params, state,
                                           jnp.asarray(ids))
                n = real_rows if real_rows is not None else len(ids)
                # One extra scalar in the readback we do anyway. The
                # prefill-emitted token (one per row, no window ran for
                # it) is excluded: 1.0 = windows accepted nothing,
                # spec_tokens+1 = full acceptance. Rows finishing early
                # pull the mean below 1 (they emit 0 in later windows) —
                # the honest aggregate. Only the first `real_rows` count:
                # batch-bucket filler rows' degenerate speculation must
                # not skew the reading.
                with intended_transfer():
                    windows = max(1, int(jax.device_get(fin.windows)))
                    result = jax.device_get(result)
                self.last_spec_tokens_per_window = float(
                    (np.sum(result.lengths[:n]) - n) / (windows * n)
                )
                return result
            else:
                result, _ = self._decode(self.params, state)
        with intended_transfer():  # the call's one sanctioned readback
            return jax.device_get(result)

    def answer_batch(self, prompts: Sequence[str]) -> List[str]:
        """Prompts in, decoded answers out.

        Groups larger than the biggest batch bucket run as several device
        batches.
        """
        if not prompts:
            return []
        cap = max(self.config.batch_buckets)
        answers: List[str] = []
        for start in range(0, len(prompts), cap):
            chunk = prompts[start : start + cap]
            ids, mask, _ = self.encode_prompts(chunk)
            result = self.generate_ids(ids, mask, real_rows=len(chunk))
            for i in range(len(chunk)):
                n = int(result.lengths[i])
                # Host-side numpy after generate_ids' readback, not a
                # device sync.  # lint: disable-next=no-host-sync-in-dispatch
                toks = [t for t in result.tokens[i, :n].tolist()
                        if t != self.tokenizer.eos_id]
                answers.append(self.tokenizer.decode(toks))
        return answers
