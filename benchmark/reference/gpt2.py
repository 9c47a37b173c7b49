"""Plain reference of the GPT-2 architecture: forward, next-token loss.

Written from the published description (Radford et al. 2019, "Language
Models are Unsupervised Multitask Learners", and the released
``config.json``): learned token and position embeddings; pre-LayerNorm
blocks of causal multi-head attention and a 4x feed-forward with the
tanh approximation of GELU ("gelu_new"); a final LayerNorm; the output
head tied to the token embedding. Straightforward ``jax.numpy`` in
float32 at the highest matmul precision — no kernel, no cache, no
batching — and nothing imported from the program. Parameters arrive under GPT-2's own names (``wte``, ``wpe``,
``h.*``, ``ln_f``) with the blocks stacked on a leading layer axis; the
family adapter maps the program's tree onto them.

Departures from the published model, all forced by what is run:
- no dropout (the program trains without it; the configuration file
  lists the three ``*_pdrop`` keys as changed);
- the softmax runs over as many vocabulary rows as ``wte`` has, so a
  table padded to a multiple of 128 is scored over its padded rows too,
  exactly as the program scores it.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def _layer_norm(x, g, b, eps):
    mu = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(x - mu), axis=-1, keepdims=True)
    return (x - mu) / jnp.sqrt(var + eps) * g + b


def _gelu_new(x):
    return 0.5 * x * (1.0 + jnp.tanh(
        jnp.sqrt(2.0 / jnp.pi) * (x + 0.044715 * x ** 3)))


def _block(x, p, n_head, eps):
    """One block on one sequence ``x [s, h]``."""
    s, h = x.shape
    d = h // n_head
    a = _layer_norm(x, p["ln_1"]["g"], p["ln_1"]["b"], eps)
    qkv = a @ p["attn"]["c_attn"]["w"] + p["attn"]["c_attn"]["b"]
    q, k, v = jnp.split(qkv, 3, axis=-1)
    heads = lambda t: t.reshape(s, n_head, d).transpose(1, 0, 2)
    q, k, v = heads(q), heads(k), heads(v)
    scores = q @ k.transpose(0, 2, 1) / jnp.sqrt(jnp.float32(d))
    causal = jnp.tril(jnp.ones((s, s), bool))
    scores = jnp.where(causal, scores, -jnp.inf)
    ctx = jax.nn.softmax(scores, axis=-1) @ v
    ctx = ctx.transpose(1, 0, 2).reshape(s, h)
    x = x + ctx @ p["attn"]["c_proj"]["w"] + p["attn"]["c_proj"]["b"]
    m = _layer_norm(x, p["ln_2"]["g"], p["ln_2"]["b"], eps)
    m = _gelu_new(m @ p["mlp"]["c_fc"]["w"] + p["mlp"]["c_fc"]["b"])
    return x + m @ p["mlp"]["c_proj"]["w"] + p["mlp"]["c_proj"]["b"]


def logits(params, tokens, *, n_head: int, eps: float):
    """``tokens [s]`` int32 -> logits ``[s, rows of wte]`` float32."""
    with jax.default_matmul_precision("highest"):
        params = jax.tree.map(lambda t: t.astype(jnp.float32), params)
        s = tokens.shape[0]
        x = params["wte"][tokens] + params["wpe"][:s]

        def body(x, p):
            return _block(x, p, n_head, eps), None

        # the layer loop as a scan over the stacked blocks: the same
        # arithmetic as a Python loop, compiled once for any depth.
        # jax.checkpoint changes no arithmetic either: it only lets the
        # gradient of a full-depth sequence fit beside a train state
        x, _ = jax.lax.scan(jax.checkpoint(body), x, params["h"])
        x = _layer_norm(x, params["ln_f"]["g"], params["ln_f"]["b"], eps)
        return x @ params["wte"].T


def token_logprobs(params, tokens, *, n_head: int, eps: float):
    """Log-probability the model gives ``tokens[i + 1]`` after
    ``tokens[: i + 1]``, for every i: ``[s - 1]`` float32."""
    lp = jax.nn.log_softmax(logits(params, tokens, n_head=n_head, eps=eps),
                            axis=-1)
    return jnp.take_along_axis(lp[:-1], tokens[1:, None], axis=-1)[:, 0]


def loss(params, tokens, targets, *, n_head: int, eps: float):
    """Mean next-token cross entropy of one sequence."""
    lp = jax.nn.log_softmax(logits(params, tokens, n_head=n_head, eps=eps),
                            axis=-1)
    return -jnp.mean(jnp.take_along_axis(lp, targets[:, None], axis=-1))
