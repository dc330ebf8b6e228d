"""A model kind for the harness's own tests, reference side only: a
mean-pooled embedding classifier whose auxiliary training loss is
``LAMBDA / 2 * |decay|^2`` over a leaf the logits never read, so that
the leaf's gradient in a client step is ``LAMBDA * decay`` and nothing
else."""
import jax
import jax.numpy as jnp

LAMBDA = 0.25


def model_dict(proto, config):
    return {"name": proto["name"], "d_model": int(proto["dim"]),
            "vocab_size": int(config["vocab_size"]),
            "seq_len": int(config["max_position_embeddings"]),
            "n_classes": int(config["num_labels"])}


def init_params(key, model, dtype):
    d, c = model["d_model"], model["n_classes"]
    k = jax.random.split(key, 3)
    p = {"embed": jax.random.normal(k[0], (model["vocab_size"], d)),
         "head": {"w": jax.random.normal(k[1], (d, c)), "b": jnp.zeros(c)},
         "decay": jax.random.normal(k[2], (d,))}
    return jax.tree.map(lambda a: a.astype(dtype), p)


def forward(params, x, model):
    logits = jnp.mean(params["embed"][x], 1) @ params["head"]["w"] \
        + params["head"]["b"]
    return logits, 0.5 * LAMBDA * jnp.sum(params["decay"] ** 2)


def forward_flops_per_token(model):
    return 2.0 * model["d_model"] * model["n_classes"]
