"""BERT relevance gate: is a student query related to their assignment?

Reference behavior (GUI_RAFT_LLM_SourceCode/lms_server.py:97-104, 1256-1270):
embed query and assignment text with BERT, mean-pool, cosine-compare against
threshold 0.6 — but the model is re-loaded from disk on every request
(defect D4). Here the encoder is loaded once, jitted once per text bucket,
and runs on the same device mesh as the tutoring model.
"""

from __future__ import annotations

import dataclasses
import logging
from typing import Any, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..models import bert, convert
from ..parallel import mesh as mesh_lib
from ..parallel import partition
from ..utils import tokenizer as tok_lib
from .generate import pick_bucket
from .spans import named_partial

log = logging.getLogger(__name__)


@dataclasses.dataclass
class GateConfig:
    model: str = "bert-base-uncased"  # or "tiny"
    checkpoint: Optional[str] = None  # .safetensors (HF layout)
    vocab_path: Optional[str] = None
    threshold: float = 0.6            # reference lms_server.py:1267
    length_buckets: Tuple[int, ...] = (64, 128, 256, 512)
    tp: int = 1
    # Weight-only int8 (models/quant.py) — same near-lossless recipe as the
    # tutoring engine; cosine similarity is scale-tolerant by construction.
    quant: Optional[str] = None
    dtype: Any = jnp.bfloat16
    seed: int = 1


class RelevanceGate:
    def __init__(self, config: GateConfig, devices: Optional[Sequence] = None):
        self.config = config
        if config.model == "tiny":
            self.cfg = bert.BertConfig.tiny(dtype=config.dtype)
        else:
            self.cfg = bert.BertConfig.base_uncased(dtype=config.dtype)
        self.mesh = mesh_lib.make_mesh({"tp": config.tp, "dp": -1},
                                       devices=devices)
        self.tokenizer = tok_lib.load_bert_tokenizer(config.vocab_path)
        if self.tokenizer.vocab_size > self.cfg.vocab_size:
            raise ValueError("tokenizer vocab exceeds model vocab")
        if config.checkpoint:
            sd = convert.load_safetensors(config.checkpoint)
            params = convert.bert_params_from_hf(sd, self.cfg)
        else:
            log.warning("no BERT checkpoint configured — random init")
            params = bert.init_params(jax.random.key(config.seed), self.cfg)
        if config.quant:
            if config.quant != "int8":
                raise ValueError(f"unsupported quant mode {config.quant!r}")
            from ..models import quant as quant_lib

            params = quant_lib.quantize_params(params, "bert")
        self.params = partition.shard_tree(params, self.mesh, partition.BERT_RULES)
        self._embed = jax.jit(named_partial(bert.embed, cfg=self.cfg))
        # Context (assignment text) embeddings are static per student and
        # re-checked on every query; caching them halves the per-query gate
        # compute — the reference re-loads the whole MODEL per request
        # (lms_server.py:1258-1260), this caches the embedding too. The
        # lock guards the miss path: check() runs on the server's executor
        # threads, and an unlocked len/clear/insert race would evict
        # entries concurrent misses just computed.
        import threading

        self._ctx_cache: dict = {}  # guarded-by: _ctx_lock
        self._ctx_lock = threading.Lock()

    def _encode(self, texts: Sequence[str]) -> Tuple[np.ndarray, np.ndarray]:
        limit = self.cfg.max_position_embeddings
        token_lists = [
            self.tokenizer.encode(t, add_special_tokens=True)[:limit] for t in texts
        ]
        longest = max(len(t) for t in token_lists)
        bucket = min(pick_bucket(longest, self.config.length_buckets), limit)
        ids = np.full((len(texts), bucket), self.tokenizer.pad_id, np.int32)
        mask = np.zeros((len(texts), bucket), np.int32)
        for i, toks in enumerate(token_lists):
            toks = toks[:bucket]
            ids[i, : len(toks)] = toks  # BERT: right-padding
            mask[i, : len(toks)] = 1
        return ids, mask

    def embed_texts(self, texts: Sequence[str]) -> np.ndarray:
        ids, mask = self._encode(texts)
        with self.mesh:
            out = self._embed(
                self.params, input_ids=jnp.asarray(ids),
                attention_mask=jnp.asarray(mask),
            )
        return np.asarray(jax.device_get(out))

    def check(self, query: str, context: str) -> Tuple[bool, float]:
        """(passes_gate, cosine_similarity) — reference threshold 0.6.

        The context embedding is cached by text (bounded; cleared wholesale
        at 256 entries), so a student's Nth query embeds only the query. A
        miss embeds [query, context] in ONE batched call — the same single
        dispatch the uncached path always cost — and caches the context
        half. Mask-weighted mean pooling makes the embedding independent of
        the padding bucket, so cached (context-alone) and joint embeddings
        agree (pinned in tests/test_quant.py).
        """
        ctx_emb = self._ctx_cache.get(context)
        if ctx_emb is None:
            emb = self.embed_texts([query, context])
            q_emb, ctx_emb = emb[0], emb[1]
            with self._ctx_lock:
                if len(self._ctx_cache) >= 256:
                    self._ctx_cache.clear()
                self._ctx_cache[context] = ctx_emb
        else:
            q_emb = self.embed_texts([query])[0]
        sim = float(
            np.dot(q_emb, ctx_emb)
            / max(float(np.linalg.norm(q_emb) * np.linalg.norm(ctx_emb)), 1e-12)
        )
        return sim >= self.config.threshold, sim

    def warmup(self) -> None:
        self.embed_texts(["warmup"])
