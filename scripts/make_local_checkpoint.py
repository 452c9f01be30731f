"""Build local HF-format serving artifacts: checkpoint + real BPE vocab.

This image has zero network egress and no HF cache, so pretrained GPT-2
weights are unobtainable. What CAN be real offline:

- the checkpoint FORMAT and loading path: a full-size HF `GPT2LMHeadModel`
  state_dict (seeded random weights) written to `.safetensors`, exactly the
  artifact `models.convert.load_safetensors` + `gpt2_params_from_hf`
  consume in production;
- the tokenizer: a REAL byte-level BPE trained with the HF `tokenizers`
  trainer on this repo's own text (docs + sources, nothing outside the
  checkout, so every machine trains the same vocab), emitting the standard
  `vocab.json`/`merges.txt` our `BPETokenizer` loads.

The bench and servers then run the identical code path a user with hub
access runs — point `--checkpoint/--vocab/--merges` at downloaded files and
nothing else changes. Reference analogue: GUI_RAFT_LLM_SourceCode/
tutoring_server.py:10-12 (`GPT2LMHeadModel.from_pretrained("gpt2")`).

Usage: python scripts/make_local_checkpoint.py [--out data/gpt2-local]
"""

from __future__ import annotations

import argparse
import glob
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def build_corpus(out_path: str) -> str:
    """Concatenate the repo's own prose/code into a BPE training corpus
    (files of the checkout only: the same text on every machine)."""
    sources: list[str] = []
    for pattern in (
        f"{REPO}/*.md",
        f"{REPO}/distributed_lms_raft_llm_tpu/**/*.py",
        f"{REPO}/tests/*.py",
        f"{REPO}/scripts/*.py",
    ):
        sources.extend(sorted(glob.glob(pattern, recursive=True)))
    with open(out_path, "w", encoding="utf-8") as out:
        for src in sources:
            with open(src, encoding="utf-8", errors="ignore") as f:
                out.write(f.read())
                out.write("\n")
    return out_path


def build_bert_local(out_dir: str, seed: int = 0,
                     vocab_size: int = 30522) -> None:
    """data/bert-local: WordPiece vocab.txt trained on local text + a
    full-size HF-layout BertModel `.safetensors` (seeded random weights)
    consumed through the identical `convert.bert_params_from_hf` path the
    gate uses for real pretrained weights. Reference analogue:
    GUI_RAFT_LLM_SourceCode/lms_server.py:1258-1260 (`bert-base-uncased`
    loaded for the relevance gate)."""
    os.makedirs(out_dir, exist_ok=True)
    ckpt = os.path.join(out_dir, "model.safetensors")
    vocab = os.path.join(out_dir, "vocab.txt")

    if not os.path.exists(vocab):
        import tokenizers

        corpus = build_corpus(os.path.join(out_dir, "corpus.txt"))
        wp = tokenizers.BertWordPieceTokenizer(lowercase=True)
        wp.train([corpus], vocab_size=vocab_size, min_frequency=2,
                 special_tokens=["[PAD]", "[UNK]", "[CLS]", "[SEP]", "[MASK]"])
        wp.save_model(out_dir)
        os.remove(corpus)
        print(f"trained WordPiece vocab: {wp.get_vocab_size()} tokens -> {vocab}")

    if not os.path.exists(ckpt):
        import torch
        import transformers

        from distributed_lms_raft_llm_tpu.models import convert

        torch.manual_seed(seed)
        model = transformers.BertModel(
            transformers.BertConfig()  # bert-base-uncased architecture
        )
        sd = {
            k: v.detach().cpu().numpy()
            for k, v in model.state_dict().items()
            if not k.startswith("pooler.")  # mean-pooled gate: pooler unused
        }
        convert.save_safetensors(ckpt, sd)
        n = sum(v.size for v in sd.values())
        print(f"wrote bert-base checkpoint: {n/1e6:.0f}M params -> {ckpt}")


def build_gpt2_vocab(out_dir: str, vocab_size: int = 50257) -> tuple:
    """Byte-level BPE `vocab.json` + `merges.txt` trained on the repo's own
    text (idempotent); returns the two paths. Needs `tokenizers` only —
    chip_smoke.py builds its serving vocab with this, JAX-free."""
    os.makedirs(out_dir, exist_ok=True)
    vocab = os.path.join(out_dir, "vocab.json")
    merges = os.path.join(out_dir, "merges.txt")
    if not (os.path.exists(vocab) and os.path.exists(merges)):
        import tokenizers

        corpus = build_corpus(os.path.join(out_dir, "corpus.txt"))
        bpe = tokenizers.ByteLevelBPETokenizer()
        bpe.train([corpus], vocab_size=vocab_size, min_frequency=2,
                  special_tokens=["<|endoftext|>"], show_progress=False)
        bpe.save_model(out_dir)
        os.remove(corpus)
        print(f"trained BPE vocab: {bpe.get_vocab_size()} tokens -> {vocab}",
              file=sys.stderr)
    return vocab, merges


def build_gpt2_local(out_dir: str, model: str = "gpt2", seed: int = 0,
                     vocab_size: int = 50257) -> None:
    """data/gpt2-local: byte-level BPE vocab/merges trained on local text +
    a full-size HF-layout GPT2LMHeadModel `.safetensors` (seeded random
    weights) consumed through the identical `convert.gpt2_params_from_hf`
    path pretrained weights use."""
    build_gpt2_vocab(out_dir, vocab_size)
    ckpt = os.path.join(out_dir, "model.safetensors")

    if not os.path.exists(ckpt):
        import torch
        import transformers

        from distributed_lms_raft_llm_tpu.models import convert

        arch = {
            "gpt2": dict(),
            "gpt2-medium": dict(n_embd=1024, n_layer=24, n_head=16),
            "gpt2-large": dict(n_embd=1280, n_layer=36, n_head=20),
        }[model]
        torch.manual_seed(seed)
        hf = transformers.GPT2LMHeadModel(transformers.GPT2Config(**arch))
        sd = {
            k: v.detach().cpu().numpy()
            for k, v in hf.state_dict().items()
            if k != "lm_head.weight"  # tied to wte
        }
        convert.save_safetensors(ckpt, sd)
        n = sum(v.size for v in sd.values())
        print(f"wrote {model} checkpoint: {n/1e6:.0f}M params -> {ckpt}")


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", default="data/gpt2-local")
    ap.add_argument("--model", default="gpt2",
                    choices=["gpt2", "gpt2-medium", "gpt2-large"])
    ap.add_argument("--vocab-size", type=int, default=50257)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--bert-out", default="data/bert-local",
                    help="BERT gate artifact directory ('' skips)")
    args = ap.parse_args()

    if args.bert_out:
        build_bert_local(args.bert_out, seed=args.seed)
    build_gpt2_local(args.out, model=args.model, seed=args.seed,
                     vocab_size=args.vocab_size)


if __name__ == "__main__":
    main()
