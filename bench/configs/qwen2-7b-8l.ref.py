"""Plain reference of Qwen2 (arXiv:2407.10671; hf Qwen/Qwen2-7B), and the
benchmark's own weights for it.

The forward pass follows the published architecture: token embedding;
per layer RMSNorm, one QKV projection with bias, rotary embedding on the
two halves of each head (``rotate_half``, theta from the config), causal
grouped-query attention, output projection, residual; RMSNorm, SwiGLU MLP
(``down(silu(gate(h)) * up(h))``), residual; a final RMSNorm and an
untied LM head.  It runs in float32 at ``highest`` matmul precision,
layer by layer and in blocks of queries, so it fits beside the weights.
It imports nothing of the program under test.

``control_logits`` is the same forward pass with every weight matmul
computed in 8 bits (weights per output channel, activations per token,
both symmetric): the lower precision a serving change might be tempted
by.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

#: query rows per attention block of the reference
Q_BLOCK = 1024
#: sequences are padded to a multiple of this, so few shapes compile
PAD = 512


def _sizes(cfg):
    d, hq, hkv = cfg["hidden_size"], cfg["num_attention_heads"], cfg["num_key_value_heads"]
    return d, hq, hkv, d // hq, cfg["intermediate_size"], cfg["vocab_size"]


def make_weights(key, cfg):
    """Seeded weights in the dtype they are served in, made on the device
    in one call: matrices normal with the config's ``initializer_range``
    as standard deviation, biases likewise, norm scales ``1 + 0.1 N(0,1)``
    in float32.  Layer weights are stacked on a leading layer axis."""
    d, hq, hkv, dh, f, v = _sizes(cfg)
    n = cfg["num_hidden_layers"]
    dt = jnp.dtype(cfg["torch_dtype"])
    std = cfg["initializer_range"]
    shapes = {
        "embed": ((v, d), dt), "lm_head": ((d, v), dt),
        "final_norm": ((d,), jnp.float32),
        "ln1": ((n, d), jnp.float32), "ln2": ((n, d), jnp.float32),
        "w_qkv": ((n, d, (hq + 2 * hkv) * dh), dt), "b_qkv": ((n, (hq + 2 * hkv) * dh), dt),
        "w_o": ((n, hq * dh, d), dt),
        "w_gate": ((n, d, f), dt), "w_up": ((n, d, f), dt), "w_down": ((n, f, d), dt),
    }

    def make(k):
        out = {}
        for i, (name, (shape, dtype)) in enumerate(sorted(shapes.items())):
            z = jax.random.normal(jax.random.fold_in(k, i), shape, jnp.float32)
            if name in ("final_norm", "ln1", "ln2"):
                out[name] = 1.0 + 0.1 * z
            else:
                out[name] = (std * z).astype(dtype)
        return out

    return jax.jit(make)(key)


def _rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * scale


def _rope(x, pos, theta):
    dh = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, dh, 2, dtype=jnp.float32) / dh)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., : dh // 2], x[..., dh // 2:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], axis=-1)


def _q8(a, axis):
    """Symmetric 8-bit rounding along ``axis`` (the scale's axis kept)."""
    s = jnp.max(jnp.abs(a), axis=axis, keepdims=True) / 127.0
    s = jnp.where(s == 0, 1.0, s)
    return jnp.round(a / s).clip(-127, 127) * s


def _mm(a, w, low: bool):
    w = w.astype(jnp.float32)
    if low:
        a, w = _q8(a, -1), _q8(w, 0)
    return a @ w


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _layer(x, w, cfg_items, low):
    cfg = dict(cfg_items)
    d, hq, hkv, dh, f, v = _sizes(cfg)
    t = x.shape[0]
    pos = jnp.arange(t)
    with jax.default_matmul_precision("highest"):
        h = _rms(x, w["ln1"], cfg["rms_norm_eps"])
        qkv = _mm(h, w["w_qkv"], low) + w["b_qkv"].astype(jnp.float32)
        q = qkv[:, : hq * dh].reshape(t, hq, dh)
        k = qkv[:, hq * dh:(hq + hkv) * dh].reshape(t, hkv, dh)
        vv = qkv[:, (hq + hkv) * dh:].reshape(t, hkv, dh)
        q, k = _rope(q, pos, cfg["rope_theta"]), _rope(k, pos, cfg["rope_theta"])
        k, vv = jnp.repeat(k, hq // hkv, axis=1), jnp.repeat(vv, hq // hkv, axis=1)
        outs = []
        for lo in range(0, t, Q_BLOCK):
            qb = q[lo:lo + Q_BLOCK]
            s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
            qpos = lo + jnp.arange(qb.shape[0])
            s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
            p = jax.nn.softmax(s, axis=-1)
            outs.append(jnp.einsum("hqk,khd->qhd", p, vv).reshape(-1, hq * dh))
        x = x + _mm(jnp.concatenate(outs, axis=0), w["w_o"], low)
        h = _rms(x, w["ln2"], cfg["rms_norm_eps"])
        g = _mm(h, w["w_gate"], low)
        x = x + _mm(jax.nn.silu(g) * _mm(h, w["w_up"], low), w["w_down"], low)
    return x


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _head(x, first, final_norm, lm_head, cfg_items, low):
    """Logits of the ``PAD`` rows from ``first`` on."""
    cfg = dict(cfg_items)
    rows = jax.lax.dynamic_slice_in_dim(jnp.pad(x, ((0, PAD), (0, 0))), first, PAD)
    with jax.default_matmul_precision("highest"):
        return _mm(_rms(rows, final_norm, cfg["rms_norm_eps"]), lm_head, low)


def _forward(weights, cfg, seq, first, low):
    seq = np.asarray(seq, np.int32)
    n = len(seq)
    t = -(-n // PAD) * PAD
    toks = np.zeros(t, np.int32)
    toks[:n] = seq  # padding sits after the sequence: causal rows before it ignore it
    items = tuple(sorted((k, v) for k, v in cfg.items() if isinstance(v, (int, float, str))))
    x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    layer_keys = ("ln1", "ln2", "w_qkv", "b_qkv", "w_o", "w_gate", "w_up", "w_down")
    for i in range(cfg["num_hidden_layers"]):
        x = _layer(x, {k: weights[k][i] for k in layer_keys}, items, low)
    out = []
    for lo in range(first, n, PAD):
        lg = _head(x, lo, weights["final_norm"], weights["lm_head"], items, low)
        out.append(np.asarray(lg[: min(PAD, n - lo)]))
    return np.concatenate(out)


def logits(weights, cfg, seq, first):
    """Float32 logits at positions ``first .. len(seq) - 1`` of ``seq``."""
    return _forward(weights, cfg, seq, first, low=False)


def control_logits(weights, cfg, seq, first):
    """The same logits with every weight matmul computed in 8 bits."""
    return _forward(weights, cfg, seq, first, low=True)
