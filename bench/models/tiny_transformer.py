"""Model kind ``tiny_transformer``: the program's pre-norm transformer
encoder classifier (``core/nets.py:tiny_transformer``), the kind of every
configuration without a ``"model"`` key.

A prototype of ``bench/configs/<config>.json`` gives ``dim``,
``n_layers``, ``n_heads`` and ``hidden_dim``, which must be ``4 * dim``:
the program's feed-forward has no other width.  The configuration gives
the vocabulary, the sequence length and the classes.

The reference's weights carry the program's leaf paths (``embed``,
``pos``, ``head/w``, ``head/b``, ``layer_<l>/wqkv``, ``wo``, ``w1``,
``w2``, ``ln1``, ``ln2``): ``compare.py`` matches the program's per-leaf
changes to the reference's by key.  The kind has no auxiliary loss.

Model FLOPs per token follow the usual count: a matmul of an [m, k] by a
[k, n] operand is 2·m·k·n, a token's forward pass multiplies by every
non-embedding weight once (2·N) and attends over the sequence (2·s·d for
QK^T and 2·s·d for AV per layer).  The embedding and position tables are
gathered, not multiplied.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def model_dict(proto: dict, config: dict) -> dict:
    if int(proto["hidden_dim"]) != 4 * int(proto["dim"]):
        raise ValueError(f"{proto['name']}: the model's feed-forward width "
                         f"is 4 x dim")
    return {"name": proto["name"], "d_model": int(proto["dim"]),
            "n_layers": int(proto["n_layers"]),
            "n_heads": int(proto["n_heads"]),
            "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "n_classes": int(config["num_labels"])}


def net(bundle, model: dict):
    from repro.api.registries import get_model
    return get_model("tiny_transformer")(
        bundle, d_model=model["d_model"], n_layers=model["n_layers"],
        n_heads=model["n_heads"], name=model["name"])


def init_params(key, model: dict, dtype) -> dict:
    """Weights from ``key``: normal draws scaled by 1/sqrt(fan-in), in the
    order one split of ``key`` hands them out."""
    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    vocab, seq, n_cls = (int(model["vocab_size"]), int(model["seq_len"]),
                         int(model["n_classes"]))
    ks = jax.random.split(key, 3 + 4 * n_layers)
    nrm = jax.random.normal
    p = {"embed": nrm(ks[0], (vocab, d)) * 0.05,
         "pos": nrm(ks[1], (seq, d)) * 0.05,
         "head": {"w": nrm(ks[2], (d, n_cls)) * (1.0 / math.sqrt(d)),
                  "b": jnp.zeros((n_cls,))}}
    for l in range(n_layers):
        k = ks[3 + 4 * l:7 + 4 * l]
        s = 1.0 / math.sqrt(d)
        p[f"layer_{l}"] = {
            "wqkv": nrm(k[0], (d, 3 * d)) * s,
            "wo": nrm(k[1], (d, d)) * s,
            "w1": nrm(k[2], (d, 4 * d)) * s,
            "w2": nrm(k[3], (4 * d, d)) * (1.0 / math.sqrt(4 * d)),
            "ln1": jnp.ones((d,)), "ln2": jnp.ones((d,))}
    return jax.tree.map(lambda a: a.astype(dtype), p)


def _rms(w, x):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + 1e-6) * w


def forward(params: dict, x, model: dict):
    """(logits [B, C], 0.0): token + position embedding, then per layer
    h += Wo attn(RMS(h)); h += W2 gelu(W1 RMS(h)); mean-pool; linear head."""
    n_heads, n_layers = int(model["n_heads"]), int(model["n_layers"])
    b, s = x.shape
    h = params["embed"][x] + params["pos"][None, :s]
    d = h.shape[-1]
    hd = d // n_heads
    for l in range(n_layers):
        p = params[f"layer_{l}"]
        y = _rms(p["ln1"], h)
        q, k, v = jnp.split(y @ p["wqkv"], 3, axis=-1)
        q, k, v = (a.reshape(b, s, n_heads, hd) for a in (q, k, v))
        att = jax.nn.softmax(
            jnp.einsum("bshd,bthd->bhst", q, k) / math.sqrt(hd), axis=-1)
        h = h + jnp.einsum("bhst,bthd->bshd", att, v).reshape(b, s, d) \
            @ p["wo"]
        h = h + jax.nn.gelu(_rms(p["ln2"], h) @ p["w1"]) @ p["w2"]
    return jnp.mean(h, axis=1) @ params["head"]["w"] + params["head"]["b"], 0.0


def non_embedding_params(model: dict) -> int:
    d, n_layers = int(model["d_model"]), int(model["n_layers"])
    n_cls = int(model["n_classes"])
    per_layer = 3 * d * d + d * d + 4 * d * d + 4 * d * d + 2 * d
    return n_layers * per_layer + d * n_cls + n_cls


def forward_flops_per_token(model: dict) -> float:
    d, n_layers, s = (int(model["d_model"]), int(model["n_layers"]),
                      int(model["seq_len"]))
    return 2.0 * non_embedding_params(model) + 4.0 * n_layers * s * d
