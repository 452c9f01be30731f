"""Model registry: one place mapping serving presets to model families.

The engine (engine/engine.py, engine/generate.py) is model-agnostic — it
drives any family exposing the same functional surface:

    init_params(rng, cfg) -> params
    forward(params, cfg, ids, cache=, positions=, kv_mask=, rows=)
        -> (logits, cache)
    init_cache(cfg, batch, max_len, dtype=, groups=) -> KVCache
    params_from_hf(state_dict, cfg) -> params

`rows` ([B] int32, ragged `cache.length` only; default: row i for batch
element i) names the cache rows a batch narrower than the cache addresses:
its keys and values are scattered into those rows in place, it attends
over those rows alone, and every other row comes back as it went in. The
paged engine's prefill chunk is such a batch, one staged slot of the live
multi-slot cache (`engine/paged.py` `_admission_chunk`).

`groups` is how many head groups a served cache keeps as the axis `tp`
shards (the engines pass their tp ways): a family whose planes would tile
padded as `[L, B, H, T, Dh]` declares them `[L, B, G, T, (H/G)*Dh]` then
(`models/common.py` `folds_heads`: GPT-2's int8 planes), and its `forward`
takes either form; every other family ignores it.

The reference hardcodes one architecture behind `from_pretrained("gpt2")`
(reference: GUI_RAFT_LLM_SourceCode/tutoring_server.py:10); here presets
cover the GPT-2 family (BASELINE configs 1-4), Llama (config 5), the
home-made GPT-2 with routed experts, Arcee's afmoe (Trinity-Mini), SK
Telecom's axk1 (A.X-K1: latent attention, a share of a layer's experts) and
NVIDIA's nemotron_h (Nemotron-3-Nano: Mamba-2 blocks whose recurrent state
lives in the cache beside one attention block's keys and values, relu^2
experts) and Moonshot's kimi_linear (Kimi-Linear: Kimi Delta Attention
layers whose matrix state lives in the cache beside the latent plane of its
MLA layers: a latent cache AND a recurrent state) and OpenBMB's
minicpm_sala (MiniCPM-SALA: block-sparse attention layers that choose the
blocks they read through a plane of pooled keys kept beside the keys, and
Lightning linear-attention layers whose matrix state lives in the cache:
keys, a selector's cache AND a recurrent state) and Liquid AI's lfm2_moe
(LFM2-8B-A1B: gated short-convolution layers whose whole state is a row's
last two inputs, `KVCache.conv` with NO `ssm` plane, beside grouped-query
attention layers' keys and values, and a layer's routed experts held whole).
"""

from __future__ import annotations

from typing import Any, Callable, NamedTuple, Tuple

from . import (
    afmoe,
    axk1,
    convert,
    gpt2,
    kimi_linear,
    lfm2,
    llama,
    minicpm_sala,
    moe,
    nemotron_h,
)


class ModelFamily(NamedTuple):
    name: str  # partition-rule key ("gpt2" | "llama" | ...)
    init_params: Callable
    forward: Callable
    init_cache: Callable
    params_from_hf: Callable
    # The forward also takes `live` (which tokens are real) and `aux`, and
    # then hands out `aux["counts"]`, what its passes counted on the device:
    # the paged engine tells such a family its idle lanes and pad positions
    # and reads the counts back with a dispatch's tokens. The flag was named
    # for routed experts (models/afmoe.py: an idle lane reaches no expert,
    # and the counts are its routing's) and has come to mean the SEAM: a
    # family without experts declares it too where a token that is not live
    # must move nothing and its counts come off the device
    # (models/minicpm_sala.py: a Lightning state, the sparse layers' lanes
    # and keys).
    routed: bool = False
    # The names of such a family's counts, in their order (`afmoe.COUNTERS`,
    # `minicpm_sala.COUNTERS`): the engine's counters of the same names.
    counters: Tuple[str, ...] = ()
    # The expert stacks shard their expert axis over `ep`
    # (parallel/partition.py): the engines refuse ep > 1 for the others.
    expert_parallel: bool = False
    # The cache is a latent (models/mla.py): one head, nothing for `tp`
    # to shard, and the engines refuse tp > 1.
    latent_cache: bool = False
    # The cache carries a recurrent state (`KVCache.ssm`, `.conv`:
    # models/mamba2.py, models/kda.py; either plane alone: a Lightning
    # state has no window, models/minicpm_sala.py, and a short
    # convolution's whole state IS its window, models/lfm2.py) that a
    # forward pass MOVES. The engines refuse
    # spec_tokens > 0 (a verify window cannot roll the state back past a
    # rejected draft) and tp > 1 (the state's heads are not sharded) for
    # such a family, and the paged engine resets a slot's state at staging
    # and reuses prefixes by state snapshots (engine/prefix_cache.py).
    recurrent_state: bool = False


GPT2_FAMILY = ModelFamily(
    "gpt2", gpt2.init_params, gpt2.forward, gpt2.init_cache,
    convert.gpt2_params_from_hf,
)
LLAMA_FAMILY = ModelFamily(
    "llama", llama.init_params, llama.forward, llama.init_cache,
    convert.llama_params_from_hf,
)
MOE_FAMILY = ModelFamily(
    "gpt2_moe", moe.init_params, moe.forward, moe.init_cache,
    moe.params_from_hf, expert_parallel=True,
)

AFMOE_FAMILY = ModelFamily(
    "afmoe", afmoe.init_params, afmoe.forward, afmoe.init_cache,
    afmoe.params_from_hf, routed=True, counters=afmoe.COUNTERS,
)
AXK1_FAMILY = ModelFamily(
    "axk1", axk1.init_params, axk1.forward, axk1.init_cache,
    axk1.params_from_hf, routed=True, counters=axk1.COUNTERS,
    latent_cache=True,
)
NEMOTRON_H_FAMILY = ModelFamily(
    "nemotron_h", nemotron_h.init_params, nemotron_h.forward,
    nemotron_h.init_cache, nemotron_h.params_from_hf, routed=True,
    counters=nemotron_h.COUNTERS, recurrent_state=True,
)
KIMI_LINEAR_FAMILY = ModelFamily(
    "kimi_linear", kimi_linear.init_params, kimi_linear.forward,
    kimi_linear.init_cache, kimi_linear.params_from_hf, routed=True,
    counters=kimi_linear.COUNTERS, latent_cache=True, recurrent_state=True,
)
MINICPM_SALA_FAMILY = ModelFamily(
    "minicpm_sala", minicpm_sala.init_params, minicpm_sala.forward,
    minicpm_sala.init_cache, minicpm_sala.params_from_hf, routed=True,
    counters=minicpm_sala.COUNTERS, recurrent_state=True,
)
LFM2_FAMILY = ModelFamily(
    "lfm2_moe", lfm2.init_params, lfm2.forward, lfm2.init_cache,
    lfm2.params_from_hf, routed=True, counters=lfm2.COUNTERS,
    recurrent_state=True,
)

# preset -> (family, config factory)
PRESETS = {
    "gpt2": (GPT2_FAMILY, gpt2.GPT2Config.small),
    "gpt2-medium": (GPT2_FAMILY, gpt2.GPT2Config.medium),
    "gpt2-large": (GPT2_FAMILY, gpt2.GPT2Config.large),
    "gpt2-xl": (GPT2_FAMILY, gpt2.GPT2Config.xl),
    "tiny": (GPT2_FAMILY, gpt2.GPT2Config.tiny),
    "llama3-8b": (LLAMA_FAMILY, llama.LlamaConfig.llama3_8b),
    "llama-tiny": (LLAMA_FAMILY, llama.LlamaConfig.tiny),
    "gpt2-moe": (MOE_FAMILY, moe.GPT2MoEConfig.moe_small),
    "moe-tiny": (MOE_FAMILY, moe.GPT2MoEConfig.tiny),
    "trinity-mini": (AFMOE_FAMILY, afmoe.AfmoeConfig.trinity_mini),
    "trinity-mini-1d4e": (AFMOE_FAMILY, afmoe.AfmoeConfig.trinity_mini_1d4e),
    "afmoe-tiny": (AFMOE_FAMILY, afmoe.AfmoeConfig.tiny),
    "ax-k1": (AXK1_FAMILY, axk1.AxK1Config.ax_k1),
    "ax-k1-1d4e-12of192": (AXK1_FAMILY, axk1.AxK1Config.ax_k1_1d4e_share),
    "axk1-tiny": (AXK1_FAMILY, axk1.AxK1Config.tiny),
    "nemotron3-nano": (NEMOTRON_H_FAMILY,
                       nemotron_h.NemotronHConfig.nemotron3_nano),
    "nemotron3-nano-9l-64of128": (
        NEMOTRON_H_FAMILY, nemotron_h.NemotronHConfig.nemotron3_nano_9l_share),
    "nemotronh-tiny": (NEMOTRON_H_FAMILY, nemotron_h.NemotronHConfig.tiny),
    "kimi-linear": (KIMI_LINEAR_FAMILY,
                    kimi_linear.KimiLinearConfig.kimi_linear),
    "kimi-linear-9l-64of256": (
        KIMI_LINEAR_FAMILY, kimi_linear.KimiLinearConfig.kimi_linear_9l_share),
    "kimilinear-tiny": (KIMI_LINEAR_FAMILY, kimi_linear.KimiLinearConfig.tiny),
    "minicpm-sala": (MINICPM_SALA_FAMILY,
                     minicpm_sala.MiniCPMSalaConfig.minicpm_sala),
    "minicpm-sala-8l": (MINICPM_SALA_FAMILY,
                        minicpm_sala.MiniCPMSalaConfig.minicpm_sala_8l),
    "sala-tiny": (MINICPM_SALA_FAMILY, minicpm_sala.MiniCPMSalaConfig.tiny),
    "lfm2-8b-a1b": (LFM2_FAMILY, lfm2.Lfm2MoeConfig.lfm2_8b_a1b),
    "lfm2-8b-a1b-13l": (LFM2_FAMILY, lfm2.Lfm2MoeConfig.lfm2_8b_a1b_13l),
    "lfm2-tiny": (LFM2_FAMILY, lfm2.Lfm2MoeConfig.tiny),
}


def resolve(preset: str, dtype: Any, param_dtype: Any = None) -> Tuple[ModelFamily, Any]:
    """Return (family, config) for an engine preset name."""
    if preset not in PRESETS:
        raise ValueError(
            f"unknown model preset {preset!r}; have {sorted(PRESETS)}"
        )
    family, factory = PRESETS[preset]
    return family, factory(dtype=dtype, param_dtype=param_dtype or dtype)
