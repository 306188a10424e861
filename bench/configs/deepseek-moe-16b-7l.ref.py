"""Plain reference of DeepSeekMoE (arXiv:2401.06066; hf
deepseek-ai/deepseek-moe-16b-base), as one chip's share of its experts, and
the benchmark's own weights for it.

The forward pass follows the published architecture: token embedding;
per layer RMSNorm, one QKV projection without bias, rotary embedding on
the two halves of each head (``rotate_half``, theta from the config),
causal multi-head attention, output projection, residual; RMSNorm, then
in the first ``first_k_dense_replace`` layers a SwiGLU MLP
(``down(silu(gate(h)) * up(h))``) and in the others the MoE block: the
router's softmax over all ``experts_held["of"]`` experts, the top
``num_experts_per_tok`` of them with their probabilities as gates (not
renormalised when ``norm_topk_prob`` is false), each held expert's SwiGLU
output weighted by its gate where the expert is among a token's top-k,
plus the shared experts (one SwiGLU of width ``n_shared_experts *
moe_intermediate_size``); residual; a final RMSNorm and an untied LM head.
The experts this chip does not hold add nothing, in the program and here
alike: that partial sum is what goes on to the next layer.

Departures from the hf modeling code: everything runs in float32 at
``highest`` matmul precision (the router too, which hf runs in the model's
dtype); the held experts are computed densely over every token, weighted
by a zero gate where not chosen; no auxiliary loss.  It runs layer by
layer and in blocks of queries, so it fits beside the weights, and
imports nothing of the program under test.

``control_logits`` is the same forward pass with every weight matmul
computed in 8 bits (weights per output channel, activations per token,
both symmetric).
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
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    return d, h, d // h


def make_weights(key, cfg):
    """Seeded weights in the dtype they are served in, made on the device
    in one call: matrices normal with the config's ``initializer_range``
    as standard deviation (the router in float32), norm scales
    ``1 + 0.1 N(0, 1)`` in float32.  Layer weights are stacked on a
    leading layer axis; the MoE layers' on one of their own."""
    d, h, dh = _sizes(cfg)
    n, n_dense = cfg["num_hidden_layers"], cfg["first_k_dense_replace"]
    if n_dense != 1:
        raise ValueError("this reference lays out one leading dense layer")
    n_moe = n - n_dense
    e, f, fd = cfg["n_routed_experts"], cfg["moe_intermediate_size"], cfg["intermediate_size"]
    fs = cfg["n_shared_experts"] * f
    v = cfg["vocab_size"]
    dt = jnp.dtype(cfg["torch_dtype"])
    f32 = jnp.float32
    std = cfg["initializer_range"]
    shapes = {
        "embed": ((v, d), dt), "lm_head": ((d, v), dt), "final_norm": ((d,), f32),
        "ln1": ((n, d), f32), "ln2": ((n, d), f32),
        "w_qkv": ((n, d, 3 * h * dh), dt), "w_o": ((n, h * dh, d), dt),
        "dense_gate": ((d, fd), dt), "dense_up": ((d, fd), dt), "dense_down": ((fd, d), dt),
        "w_router": ((n_moe, d, cfg["experts_held"]["of"]), f32),
        "w_gate": ((n_moe, e, d, f), dt), "w_up": ((n_moe, e, d, f), dt),
        "w_down": ((n_moe, e, f, d), dt),
        "shared_gate": ((n_moe, d, fs), dt), "shared_up": ((n_moe, d, fs), dt),
        "shared_down": ((n_moe, fs, d), dt),
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


def _swiglu(h, gate, up, down, low):
    return _mm(jax.nn.silu(_mm(h, gate, low)) * _mm(h, up, low), down, low)


def _attention(x, w, cfg, low):
    d, nh, dh = _sizes(cfg)
    t = x.shape[0]
    pos = jnp.arange(t)
    h = _rms(x, w["ln1"], cfg["rms_norm_eps"])
    qkv = _mm(h, w["w_qkv"], low)
    q = _rope(qkv[:, : nh * dh].reshape(t, nh, dh), pos, cfg["rope_theta"])
    k = _rope(qkv[:, nh * dh: 2 * nh * dh].reshape(t, nh, dh), pos, cfg["rope_theta"])
    vv = qkv[:, 2 * nh * dh:].reshape(t, nh, dh)
    outs = []
    for lo in range(0, t, Q_BLOCK):
        qb = q[lo:lo + Q_BLOCK]
        s = jnp.einsum("qhd,khd->hqk", qb, k) / jnp.sqrt(jnp.float32(dh))
        qpos = lo + jnp.arange(qb.shape[0])
        s = jnp.where(qpos[None, :, None] >= pos[None, None, :], s, -jnp.inf)
        p = jax.nn.softmax(s, axis=-1)
        outs.append(jnp.einsum("hqk,khd->qhd", p, vv).reshape(-1, nh * dh))
    return x + _mm(jnp.concatenate(outs, axis=0), w["w_o"], low)


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _dense_layer(x, w, cfg_items, low):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        x = _attention(x, w, cfg, low)
        h = _rms(x, w["ln2"], cfg["rms_norm_eps"])
        return x + _swiglu(h, w["dense_gate"], w["dense_up"], w["dense_down"], low)


@functools.partial(jax.jit, static_argnames=("cfg_items", "low"))
def _moe_layer(x, w, cfg_items, low):
    cfg = dict(cfg_items)
    with jax.default_matmul_precision("highest"):
        x = _attention(x, w, cfg, low)
        h = _rms(x, w["ln2"], cfg["rms_norm_eps"])
        y = _swiglu(h, w["shared_gate"], w["shared_up"], w["shared_down"], low)
        probs = jax.nn.softmax(_mm(h, w["w_router"], low), axis=-1)
        gates, idx = jax.lax.top_k(probs, cfg["num_experts_per_tok"])
        if cfg["norm_topk_prob"]:
            gates = gates / gates.sum(-1, keepdims=True)
        for j in range(w["w_gate"].shape[0]):
            gate = jnp.where(idx == cfg["held_first"] + j, gates, 0.0).sum(-1)
            y = y + gate[:, None] * _swiglu(h, w["w_gate"][j], w["w_up"][j], w["w_down"][j], low)
        return x + y


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
    flat = dict(cfg, held_first=cfg["experts_held"]["first"])
    items = tuple(sorted((k, v) for k, v in flat.items() if isinstance(v, (int, float, str))))
    x = jnp.take(weights["embed"], jnp.asarray(toks), axis=0).astype(jnp.float32)
    attn = ("ln1", "ln2", "w_qkv", "w_o")
    x = _dense_layer(x, {k: weights[k][0] for k in attn} | {
        k: weights[k] for k in ("dense_gate", "dense_up", "dense_down")}, items, low)
    moe = ("w_router", "w_gate", "w_up", "w_down", "shared_gate", "shared_up", "shared_down")
    for i in range(cfg["num_hidden_layers"] - 1):
        w = {k: weights[k][i + 1] for k in attn} | {k: weights[k][i] for k in moe}
        x = _moe_layer(x, w, items, low)
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
