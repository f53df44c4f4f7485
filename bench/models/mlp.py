"""FedAvg's multilayer perceptron: two hidden ReLU layers of ``hidden``
units over ``dim`` features, ``classes`` outputs (the MNIST 2NN at
784-200-200-10), trained on Gaussian class clusters in FedAvg's
pathological non-IID split."""
from __future__ import annotations

import math
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench.lib.fixture import DATA, sub_seed

PROGRAM = "mlp"


def shapes(model: dict) -> dict[str, tuple[int, ...]]:
    """Leaf shapes of the apps' MLP (two hidden layers, ReLU)."""
    d, h, c = int(model["dim"]), int(model["hidden"]), int(model["classes"])
    return {
        "w1": (d, h), "b1": (h,),
        "w2": (h, h), "b2": (h,),
        "w3": (h, c), "b3": (c,),
    }


def init_params(key_seed: int, model: dict, n_apps: int) -> list[dict]:
    """Every app's MLP weights, on the device, in one jitted call:
    weights N(0, 1/fan_in), biases zero, float32."""
    leaf_shapes = shapes(model)

    @partial(jax.jit, static_argnums=(1,))
    def draw(key, n):
        out = []
        for k in jax.random.split(key, n):
            ks = jax.random.split(k, 3)
            p = {}
            for i, name in enumerate(("w1", "w2", "w3")):
                shape = leaf_shapes[name]
                p[name] = jax.random.normal(ks[i], shape, jnp.float32) / math.sqrt(shape[0])
                b = "b" + name[1]
                p[b] = jnp.zeros(leaf_shapes[b], jnp.float32)
            out.append(p)
        return out

    return draw(jax.random.key(key_seed), n_apps)


def app_data(seed: int, app: int, config: dict, workers: int) -> list[tuple[np.ndarray, np.ndarray]]:
    """One app's data: ``workers`` shards of ``shard`` samples each, in
    FedAvg's pathological non-IID split: each worker holds
    ``label_shards`` distinct classes, ``shard / label_shards`` samples
    of each.  Class centres N(0, centre_scale**2 I); a sample is its
    centre plus N(0, I) noise."""
    model, shard = config["model"], int(config["shard"])
    d, c, k = int(model["dim"]), int(model["classes"]), int(config["label_shards"])
    rng = np.random.default_rng(sub_seed(seed, DATA, app))
    centres = (rng.standard_normal((c, d), dtype=np.float32) * np.float32(config["centre_scale"]))
    classes = np.stack([rng.choice(c, size=k, replace=False) for _ in range(workers)])
    y = np.repeat(classes, -(-shard // k), axis=1)[:, :shard].astype(np.int32)
    x = rng.standard_normal((workers, shard, d), dtype=np.float32)
    x += centres[y]
    return [(x[i], y[i]) for i in range(workers)]


def loss(p, batch, *, mm, dtype):
    """Cross-entropy of the MLP's logits, mean over the shard's samples."""
    x, y = batch
    x = x.astype(dtype)
    h = jax.nn.relu(mm(x, p["w1"]) + p["b1"])
    h = jax.nn.relu(mm(h, p["w2"]) + p["b2"])
    lp = jax.nn.log_softmax(mm(h, p["w3"]) + p["b3"])
    return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=1))


def flops_per_sample(model: dict) -> int:
    """Matmul operations of one local SGD step on one sample: forward
    2 MACs per weight; backward the weight gradients of all three layers
    and the input gradients of layers 2 and 3 (the data needs none).
    Biases and activations are left out."""
    d, h, c = int(model["dim"]), int(model["hidden"]), int(model["classes"])
    weights = d * h + h * h + h * c
    return 2 * weights + 2 * weights + 2 * (h * h + h * c)


def train_flops(model: dict, config: dict) -> int:
    """One commit: ``local_steps`` full-batch steps over ``shard`` samples."""
    return int(config["local_steps"]) * int(config["shard"]) * flops_per_sample(model)


def shrink(model: dict, *, hidden: int | None = None) -> dict:
    """The model with a narrower hidden layer, where ``hidden`` is given."""
    return {**model, **({"hidden": hidden} if hidden else {})}
