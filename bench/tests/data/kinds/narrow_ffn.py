"""A model kind for the harness's own tests: a pre-norm transformer
encoder classifier whose feed-forward is ``hidden_dim`` wide (twice
``dim`` in ``narrow_ffn_config.json``), which the program's
``tiny_transformer`` cannot build.  Its program net is the one the test
registers as ``bench_test_narrow_ffn``.  The reference's leaves carry
the program net's leaf paths."""
import math

import jax
import jax.numpy as jnp

REGISTRY_NAME = "bench_test_narrow_ffn"


def model_dict(proto, config):
    return {"name": proto["name"], "d_model": int(proto["dim"]),
            "d_ff": int(proto["hidden_dim"]),
            "n_layers": int(proto["n_layers"]),
            "n_heads": int(proto["n_heads"]),
            "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "n_classes": int(config["num_labels"])}


def net(bundle, model):
    from repro.api.registries import get_model
    return get_model(REGISTRY_NAME)(bundle, **{
        k: model[k] for k in ("d_model", "d_ff", "n_layers", "n_heads",
                              "name")})


def init_params(key, model, dtype):
    d, f, n_layers = model["d_model"], model["d_ff"], model["n_layers"]
    ks = jax.random.split(key, 3 + 4 * n_layers)
    nrm = jax.random.normal
    p = {"embed": nrm(ks[0], (model["vocab_size"], d)) * 0.05,
         "pos": nrm(ks[1], (model["seq_len"], d)) * 0.05,
         "head": {"w": nrm(ks[2], (d, model["n_classes"])) / math.sqrt(d),
                  "b": jnp.zeros((model["n_classes"],))}}
    for l in range(n_layers):
        k = ks[3 + 4 * l:7 + 4 * l]
        p[f"layer_{l}"] = {
            "wqkv": nrm(k[0], (d, 3 * d)) / math.sqrt(d),
            "wo": nrm(k[1], (d, d)) / math.sqrt(d),
            "w1": nrm(k[2], (d, f)) / math.sqrt(d),
            "w2": nrm(k[3], (f, d)) / math.sqrt(f),
            "ln1": jnp.ones((d,)), "ln2": jnp.ones((d,))}
    return jax.tree.map(lambda a: a.astype(dtype), p)


def forward(params, x, model):
    rms = lambda w, h: h * jax.lax.rsqrt(
        jnp.mean(h * h, -1, keepdims=True) + 1e-6) * w
    b, s = x.shape
    h = params["embed"][x] + params["pos"][None, :s]
    d, nh = model["d_model"], model["n_heads"]
    for l in range(model["n_layers"]):
        p = params[f"layer_{l}"]
        q, k, v = (a.reshape(b, s, nh, d // nh) for a in
                   jnp.split(rms(p["ln1"], h) @ p["wqkv"], 3, axis=-1))
        att = jax.nn.softmax(jnp.einsum("bshd,bthd->bhst", q, k)
                             / math.sqrt(d // nh), axis=-1)
        h = h + jnp.einsum("bhst,bthd->bshd", att, v).reshape(b, s, d) \
            @ p["wo"]
        h = h + jax.nn.gelu(rms(p["ln2"], h) @ p["w1"]) @ p["w2"]
    return jnp.mean(h, 1) @ params["head"]["w"] + params["head"]["b"], 0.0


def forward_flops_per_token(model):
    d, f, n = model["d_model"], model["d_ff"], model["n_layers"]
    c = model["n_classes"]
    params = n * (4 * d * d + 2 * d * f + 2 * d) + d * c + c
    return 2.0 * params + 4.0 * n * model["seq_len"] * d
