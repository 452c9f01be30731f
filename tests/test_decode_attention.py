"""Interpret-mode parity of the Pallas decode-attention kernel
(ops/attention.py) against the XLA reference `models.common.attend`, on
the CPU in f32. Whether the TPU's compiler accepts the kernel at real
widths is tests/test_chip_compile.py's question."""

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from distributed_lms_raft_llm_tpu.models.common import attend, repeat_kv
from distributed_lms_raft_llm_tpu.ops import attention


def _case(layers, b, h, hkv, s, dh, seed=0):
    ks = jax.random.split(jax.random.key(seed), 4)
    q = jax.random.normal(ks[0], (b, h, 1, dh), jnp.float32)
    k = jax.random.normal(ks[1], (layers, b, hkv, s, dh), jnp.float32)
    v = jax.random.normal(ks[2], (layers, b, hkv, s, dh), jnp.float32)
    # Ragged valid lengths per row, at least one key each.
    lengths = jax.random.randint(ks[3], (b,), 1, s + 1)
    mask = (jnp.arange(s)[None, :] < lengths[:, None])[:, None, None, :]
    return q, k, v, mask


@pytest.mark.parametrize(
    "layers,b,h,hkv,s,dh,budget",
    [
        (2, 2, 4, 4, 32, 16, None),          # all heads in one grid step
        (2, 2, 4, 4, 32, 16, 1),             # one KV head per grid step
        (3, 2, 8, 2, 24, 32, None),          # grouped-query: 4 q per KV head
        (3, 2, 8, 2, 24, 32, 1),             # grouped-query, split heads
        (2, 1, 20, 20, 16, 64, 10 * 4 * 16 * 128 * 4),  # 20 heads -> 2 x 10
    ],
)
def test_decode_attention_matches_attend(monkeypatch, layers, b, h, hkv, s,
                                         dh, budget):
    if budget is not None:
        monkeypatch.setattr(attention, "_KV_VMEM_BUDGET", budget)
    q, k, v, mask = _case(layers, b, h, hkv, s, dh)
    for layer in (0, layers - 1):
        got = attention.decode_attention(
            q, k, v, jnp.asarray(layer, jnp.int32),
            attention.mask_to_bias(mask), interpret=True,
        )
        want = attend(q, repeat_kv(k[layer], h // hkv),
                      repeat_kv(v[layer], h // hkv), mask)
        assert got.shape == want.shape == (b, h, 1, dh)
        np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                   rtol=1e-5, atol=1e-5)


@pytest.mark.parametrize(
    "hkv,s,dh,itemsize,want",
    [
        (12, 1024, 64, 2, 12),   # gpt2: 12 x 1 MiB fills the budget exactly
        (20, 1024, 64, 2, 10),   # gpt2-large: 20 MiB in one block is refused
        (12, 256, 64, 2, 12),
        (8, 4096, 128, 2, 2),    # a GQA llama at long context: 4 MiB a head
        (7, 1 << 20, 128, 2, 1),  # never below one head
    ],
)
def test_kv_heads_per_step_fits_the_vmem_budget(hkv, s, dh, itemsize, want):
    got = attention._kv_heads_per_step(hkv, s, dh, itemsize)
    assert got == want and hkv % got == 0
