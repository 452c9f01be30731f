"""Plain minicpm_sala forward pass: the benchmark's reference for
MiniCPM-SALA.

`jax.numpy`, float32, products at `highest` precision, one sequence at once:
no cache, no pooled plane, no kernel, no chunked form and no import from the
program. Weights come a layer at a time (`weights.Seeded.layer(l)` when the
loop reaches l). The layers are ISSUE 54's (`README.md` beside this file has
the equations; each size the published `config.json` does not carry is under
`assumed` in the configuration file):

- `h = E[ids] * scale_emb`; `h += s * mixer(N1(h))`, `h += s * SwiGLU(N2(h))`
  with `s = scale_depth / sqrt(PUBLISHED num_hidden_layers)`; final RMSNorm,
  the hidden state divided by `hidden_size / dim_model_base`, untied head;
- a `lightning-attn` layer: q, k RMS-normalised a head, then rotated
  (rotate-half, theta 10,000); THE RECURRENCE, TOKEN BY TOKEN from zeros:
  `S <- lambda_h S + k v^T`, `o = S^T q / sqrt(128)`; `lambda_h = exp(-s_h (1
  - l / (L - 1) + 1e-5))`, `s_h = 2^(-8 h / H)` for h = 1..H and l the
  layer's PUBLISHED index; `Wo (RMSNorm_head(o) * sigmoid(x Wg))`;
- a `minicpm4` layer, PER QUERY: q, k RMS-normalised a head, no rotation; a
  query at position p < `dense_len` attends causally to every key; else the
  pooled keys `c_j = mean(k[stride j : stride j + kernel])` whose last key is
  at or behind p are scored by each query head (`softmax(q . c / sqrt(128))`
  over them), the 16 heads of a key head's group summed; a block's score is
  the largest of the pooled keys whose window overlaps it; the first
  `init_blocks` and the blocks that hold the last `window_size` keys are
  taken, the best others fill the set to `topk` (a tie goes to the earlier
  block); attention is the causal softmax over the chosen blocks' keys in
  the EXPANDED form (a mask over all keys); `Wo (o * sigmoid(x Wg))`.

Departures from the published modelling code, each deliberate: linears are
stored [in, out]; dense or sparse is decided by the QUERY'S OWN POSITION
(the published prefill decides by the length of its call, the published
token-by-token decode by the position: a served chunked prefill under a
prefix cache can only be the latter); "wholly behind" counts the query's own
key as behind it; Lightning's chunked kernel is the recurrence it computes,
written as the recurrence; a pipeline stage of the layers (`layers_kept`).

`forward` returns what `compare.readings` reads, a tuple: (logits [P, V] of
the last `check.logit_positions` positions, keys [La, T, Hkv, D], values
[La, T, Hkv, D], pooled keys [La, J, Hkv, D] (c_0 .. c_{J-1}), the
Lightning states after the last token [Ll, H, K, V], the chosen blocks
[La, Hkv, T, NB] bool, `dense_len`, 0.0: a reference has no idle rows).

`CONTROLS`: the same reference with ONE stated precision a step lower
(`int8_weights`, `bf16_state`, `int8_kv`), and two of this architecture's
own mathematics: `dense_past_dense_len` (the sparse layers read every key at
every position) and `window_not_taken` (the blocks of the last `window_size`
keys are not taken for granted: they compete by score like the others).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

CONTROLS = ("int8_weights", "bf16_state", "int8_kv", "dense_past_dense_len",
            "window_not_taken")
SPARSE = "minicpm4"
QUERIES_AT_ONCE = 128
MLP_TOKENS_AT_ONCE = 4096
TAKEN = 1e9


def _rms_norm(x, gain, eps):
    return x / jnp.sqrt(jnp.mean(jnp.square(x), axis=-1, keepdims=True)
                        + eps) * gain


def _round_to_bits(x, axis, bits):
    top = float(2 ** (bits - 1) - 1)
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True) / top, 1e-8)
    return jnp.clip(jnp.round(x / s), -top, top) * s


def _rope(x, theta):
    """x [T, H, D] at positions 0..T-1, rotate-half."""
    t, _, d = x.shape
    inv = 1.0 / theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d)
    f = jnp.arange(t, dtype=jnp.float32)[:, None] * inv
    cos = jnp.concatenate([jnp.cos(f), jnp.cos(f)], axis=-1)[:, None]
    sin = jnp.concatenate([jnp.sin(f), jnp.sin(f)], axis=-1)[:, None]
    x1, x2 = x[..., :d // 2], x[..., d // 2:]
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


def log_decay(heads: int, published_index: int, published_layers: int):
    """[H]: log lambda_h of the layer at `published_index`."""
    slopes = 2.0 ** (-8.0 * np.arange(1, heads + 1, dtype=np.float64) / heads)
    return jnp.asarray(-slopes * (1.0 - published_index
                                  / (published_layers - 1) + 1e-5),
                       jnp.float32)


@functools.partial(jax.jit, static_argnames=(
    "heads", "dk", "eps", "theta", "branch", "bf16_state"))
def _lightning(x, lw, decay, *, heads, dk, eps, theta, branch,
               bf16_state=False):
    """x + branch * Lightning(N1(x)) for x [T, D], and the state after the
    last token [H, K, V]."""
    t = x.shape[0]
    a = "self_attn."
    h = _rms_norm(x, lw["input_layernorm.weight"], eps)

    def heads_of(name):
        return (h @ lw[a + name]).reshape(t, heads, dk)

    q = _rope(_rms_norm(heads_of("q_proj.weight"), lw[a + "q_norm.weight"],
                        eps), theta)
    k = _rope(_rms_norm(heads_of("k_proj.weight"), lw[a + "k_norm.weight"],
                        eps), theta)
    v = heads_of("v_proj.weight")
    lam = jnp.exp(decay)[:, None, None]

    def token(state, at):
        q_t, k_t, v_t = at
        state = lam * state + k_t[:, :, None] * v_t[:, None, :]
        if bf16_state:
            # `reduce_precision`, not a cast there and back: the TPU's
            # compiler is allowed excess precision and drops such a pair.
            state = jax.lax.reduce_precision(state, exponent_bits=8,
                                             mantissa_bits=7)
        return state, jnp.sum(state * q_t[:, :, None], axis=1) * dk ** -0.5

    state, o = jax.lax.scan(token, jnp.zeros((heads, dk, dk)), (q, k, v))
    o = _rms_norm(o, lw[a + "o_norm.weight"], eps) * jax.nn.sigmoid(
        heads_of("o_gate.weight"))
    return x + branch * (o.reshape(t, -1) @ lw[a + "o_proj.weight"]), state


@functools.partial(jax.jit, static_argnames=(
    "heads", "kv_heads", "dh", "eps", "branch", "sizes", "kv_bits", "dense",
    "window_taken"))
def _sparse(x, lw, *, heads, kv_heads, dh, eps, branch, sizes, kv_bits=None,
            dense=False, window_taken=True):
    """x + branch * BlockSparse(N1(x)) for x [T, D], the layer's keys and
    values [T, Hkv, D], its pooled keys [J, Hkv, D] and the blocks every
    query chose [Hkv, T, NB] bool."""
    kernel, stride, block, init, window, topk, dense_len = sizes
    t = x.shape[0]
    a = "self_attn."
    groups = heads // kv_heads
    h = _rms_norm(x, lw["input_layernorm.weight"], eps)
    q = _rms_norm((h @ lw[a + "q_proj.weight"]).reshape(t, heads, dh),
                  lw[a + "q_norm.weight"], eps)
    k = _rms_norm((h @ lw[a + "k_proj.weight"]).reshape(t, kv_heads, dh),
                  lw[a + "k_norm.weight"], eps)
    v = (h @ lw[a + "v_proj.weight"]).reshape(t, kv_heads, dh)
    if kv_bits:
        k, v = _round_to_bits(k, -1, kv_bits), _round_to_bits(v, -1, kv_bits)
    scale = dh ** -0.5
    # Pooled keys: c_j = mean(k[stride j : stride j + kernel]).
    nj = max((t - kernel) // stride + 1, 0)
    spans = stride * np.arange(nj)[:, None] + np.arange(kernel)[None, :]
    pooled = jnp.mean(k[spans], axis=1) if nj else jnp.zeros(
        (0, kv_heads, dh))                                     # [J, Hkv, D]
    nb = -(-t // block)
    # The pooled keys whose window overlaps block m: j from
    # (block m - kernel) // stride + 1 to (block (m + 1) - 1) // stride.
    first = (block * np.arange(nb) - kernel) // stride + 1
    reach = (block - 1) // stride - (-kernel) // stride
    over = first[:, None] + np.arange(reach)[None, :]          # [NB, reach]
    inside = (over >= 0) & (over < nj)
    over = np.clip(over, 0, max(nj - 1, 0))
    key_pos = jnp.arange(t)
    last_key = stride * jnp.arange(nj) + kernel - 1
    m = jnp.arange(nb)
    kk = k.transpose(1, 0, 2)                                  # [Hkv, T, D]
    vv = v.transpose(1, 0, 2)
    cc = pooled.transpose(1, 0, 2)                             # [Hkv, J, D]

    def some_queries(part):
        qq, pos = part                       # [Q, heads, D], [Q]
        qq = qq.reshape(-1, kv_heads, groups, dh).transpose(1, 2, 0, 3)
        if nj:
            s = jnp.einsum("ngqd,njd->ngqj", qq, cc) * scale
            vis = (last_key[None, :] <= pos[:, None])[None, None]
            s = jnp.where(vis, s, -jnp.inf)
            top = jnp.max(s, axis=-1, keepdims=True)
            e = jnp.where(vis, jnp.exp(s - jnp.where(jnp.isfinite(top), top,
                                                     0.0)), 0.0)
            p = e / jnp.maximum(jnp.sum(e, axis=-1, keepdims=True), 1e-30)
            summed = jnp.sum(p, axis=1)                        # [Hkv, Q, J]
            score = jnp.max(jnp.where(inside, summed[..., over], 0.0),
                            axis=-1)                           # [Hkv, Q, NB]
        else:
            score = jnp.zeros((kv_heads, pos.shape[0], nb))
        taken = m[None, :] < init
        if window_taken:
            taken = taken | (m[None, :] >= jnp.maximum(
                pos[:, None] - window + 1, 0) // block)
        score = jnp.where(taken[None], TAKEN, score)
        score = jnp.where((m[None, :] <= pos[:, None] // block)[None], score,
                          -1.0)
        _, picks = jax.lax.top_k(score, min(topk, nb))
        chosen = jnp.any(picks[..., None] == m, axis=-2)       # [Hkv, Q, NB]
        keys = jnp.repeat(chosen, block, axis=-1)[..., :t]
        if dense:
            keys = jnp.ones_like(keys)
        keys = keys | (pos < dense_len)[None, :, None]
        keys = keys & (key_pos[None, :] <= pos[:, None])[None]
        att = jnp.einsum("ngqd,nkd->ngqk", qq, kk) * scale
        att = jax.nn.softmax(jnp.where(keys[:, None], att, -jnp.inf), axis=-1)
        o = jnp.einsum("ngqk,nkd->ngqd", att, vv)
        return o.transpose(2, 0, 1, 3).reshape(-1, heads * dh), chosen

    qb = min(QUERIES_AT_ONCE, t)
    pad = -t % qb
    qp = jnp.pad(q, [(0, pad), (0, 0), (0, 0)]).reshape(-1, qb, heads, dh)
    pp = jnp.pad(key_pos, (0, pad), constant_values=t - 1).reshape(-1, qb)
    o, chosen = jax.lax.map(some_queries, (qp, pp))
    o = o.reshape(-1, heads * dh)[:t]
    chosen = chosen.transpose(1, 0, 2, 3).reshape(kv_heads, -1, nb)[:, :t]
    o = o * jax.nn.sigmoid(h @ lw[a + "o_gate.weight"])
    return (x + branch * (o @ lw[a + "o_proj.weight"]), k, v, pooled, chosen)


@functools.partial(jax.jit, static_argnames=("eps", "branch"))
def _mlp(x, lw, *, eps, branch):
    """x + branch * SwiGLU(N2(x)), some tokens at once."""
    t = x.shape[0]
    n = min(MLP_TOKENS_AT_ONCE, t)
    pad = -t % n

    def some(part):
        h = _rms_norm(part, lw["post_attention_layernorm.weight"], eps)
        return (jax.nn.silu(h @ lw["mlp.gate_proj.weight"])
                * (h @ lw["mlp.up_proj.weight"])) @ lw["mlp.down_proj.weight"]

    y = jax.lax.map(some, jnp.pad(x, [(0, pad), (0, 0)]).reshape(
        -1, n, x.shape[1]))
    return x + branch * y.reshape(-1, x.shape[1])[:t]


@functools.partial(jax.jit, static_argnames=("eps", "divide"))
def _head(x, gain, head, *, eps, divide):
    return (_rms_norm(x, gain, eps) / divide) @ head.T


def _int8(lw: dict) -> dict:
    """Every matrix of a layer in 8 bits, one scale per output channel."""
    for name in list(lw):
        if lw[name].ndim >= 2:
            lw[name] = _round_to_bits(lw[name], -2, 8)
    return lw


def sparse_sizes(config: dict) -> tuple:
    """(kernel, stride, block, init blocks, window, topk, dense_len)."""
    s = config["sparse_config"]
    return tuple(int(s[k]) for k in (
        "kernel_size", "kernel_stride", "block_size", "init_blocks",
        "window_size", "topk", "dense_len"))


def forward(w, ids, config: dict, control=None):
    """The tuple the module's head lists, float32, for one sequence of
    token ids [T]. `w` is a `weights.Seeded` in float32; every size is the
    configuration file's."""
    if control not in (None,) + CONTROLS:
        raise ValueError(f"no control is called {control!r}: {CONTROLS}")
    eps = float(config["rms_norm_eps"])
    published = int(config["published"]["num_hidden_layers"])
    first = int(config["layers_kept"]["first"])
    branch = float(config["scale_depth"]) / published ** 0.5
    rows = int(config["check"]["logit_positions"])
    lh = int(config["lightning_nh"])
    sparse = dict(
        heads=int(config["num_attention_heads"]),
        kv_heads=int(config["num_key_value_heads"]),
        dh=int(config["head_dim"]), eps=eps, branch=branch,
        sizes=sparse_sizes(config),
        kv_bits=8 if control == "int8_kv" else None,
        dense=control == "dense_past_dense_len",
        window_taken=control != "window_not_taken")
    lightning = dict(heads=lh, dk=int(config["lightning_head_dim"]), eps=eps,
                     theta=float(config["rope_theta"]), branch=branch,
                     bf16_state=control == "bf16_state")
    with jax.default_matmul_precision("highest"):
        ids = jnp.asarray(ids, jnp.int32)
        embed = w.embed()
        if control == "int8_weights":
            embed = _round_to_bits(embed, -1, 8)
        x = embed[ids] * float(config["scale_emb"])
        del embed
        keys, values, pooled, states, chosen = [], [], [], [], []
        for layer, mixer in enumerate(config["mixer_types"]):
            lw = w.layer(layer)
            if control == "int8_weights":
                lw = _int8(lw)
            if mixer == SPARSE:
                x, k, v, c, picks = _sparse(x, lw, **sparse)
                keys.append(k)
                values.append(v)
                pooled.append(c)
                chosen.append(picks)
            else:
                x, state = _lightning(
                    x, lw, log_decay(lh, first + layer, published),
                    **lightning)
                states.append(state)
            x = _mlp(x, lw, eps=eps, branch=branch)
            del lw
        head = w.head()
        if control == "int8_weights":
            head = _round_to_bits(head, -1, 8)
        logits = _head(x[-rows:], w.norm(), head, eps=eps,
                       divide=float(config["hidden_size"])
                       / float(config["dim_model_base"]))
        return (logits, jnp.stack(keys), jnp.stack(values),
                jnp.stack(pooled), jnp.stack(states), jnp.stack(chosen),
                sparse["sizes"][6], 0.0)
