"""Model kinds (``bench/models/<kind>.py``): the MLP kind gives the bits
the benchmark gave before kinds existed, and a kind that no bench file
names runs through the cell's spec, its deployment and the reference."""
import json
import math
import os
import sys
import types
from functools import partial

import jax
import jax.numpy as jnp
import numpy as np
import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench.lib import compare, fixture, reference  # noqa: E402
from bench.lib import spec as S  # noqa: E402
from bench.models import mlp  # noqa: E402

SEED = 2**31 + 4242


@pytest.mark.parametrize("model,params", [
    ({"dim": 16, "hidden": 64, "classes": 4}, 5_508),
    ({"dim": 32, "hidden": 4650, "classes": 8}, 21_817_808),
    ({"dim": 784, "hidden": 200, "classes": 10}, 199_210),  # FedAvg's MNIST 2NN
])
def test_mlp_sizes(model, params):
    shapes = mlp.shapes(model)
    assert sum(math.prod(s) for s in shapes.values()) == params
    assert S.n_params({"kind": "mlp", **model}) == params
    d, h, c = model["dim"], model["hidden"], model["classes"]
    per_sample = 4 * (d * h + h * h + h * c) + 2 * (h * h + h * c)
    assert mlp.flops_per_sample(model) == per_sample
    assert mlp.train_flops(model, {"local_steps": 3, "shard": 600}) == 3 * 600 * per_sample


def test_missing_kind_names_its_file():
    with pytest.raises(KeyError, match="bench/models/no_such_kind.py"):
        S.model_kind({"kind": "no_such_kind"})


# -- the parent's MLP code, verbatim: the oracle of the bits -------------------

_OVERLAY, _PLACEMENT, _WEIGHTS, _DATA, _COMPUTE, _CHURN, _ROUNDING, _FOLLOW = range(8)


def mlp_shapes(model: dict) -> dict[str, tuple[int, ...]]:
    d, h, c = int(model["dim"]), int(model["hidden"]), int(model["classes"])
    return {
        "w1": (d, h), "b1": (h,),
        "w2": (h, h), "b2": (h,),
        "w3": (h, c), "b3": (c,),
    }


def init_params(seed: int, model: dict, n_apps: int) -> list[dict]:
    shapes = mlp_shapes(model)

    @partial(jax.jit, static_argnums=(1,))
    def draw(key, n):
        out = []
        for k in jax.random.split(key, n):
            ks = jax.random.split(k, 3)
            p = {}
            for i, name in enumerate(("w1", "w2", "w3")):
                shape = shapes[name]
                p[name] = jax.random.normal(ks[i], shape, jnp.float32) / math.sqrt(shape[0])
                b = "b" + name[1]
                p[b] = jnp.zeros(shapes[b], jnp.float32)
            out.append(p)
        return out

    return draw(jax.random.key(fixture.sub_seed(seed, _WEIGHTS)), n_apps)


def app_data(seed: int, app: int, model: dict, workers: int, shard: int,
             label_shards: int, centre_scale: float) -> list[tuple[np.ndarray, np.ndarray]]:
    d, c, k = int(model["dim"]), int(model["classes"]), int(label_shards)
    rng = np.random.default_rng(fixture.sub_seed(seed, _DATA, app))
    centres = (rng.standard_normal((c, d), dtype=np.float32) * np.float32(centre_scale))
    classes = np.stack([rng.choice(c, size=k, replace=False) for _ in range(workers)])
    y = np.repeat(classes, -(-shard // k), axis=1)[:, :shard].astype(np.int32)
    x = rng.standard_normal((workers, shard, d), dtype=np.float32)
    x += centres[y]
    return [(x[i], y[i]) for i in range(workers)]


def _fp8(a):
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / 448.0
    r = (a / s).astype(jnp.float8_e4m3fn).astype(a.dtype) * s
    return a + jax.lax.stop_gradient(r - a)


def _logits(p, x, fp8: bool = False):
    mm = (lambda a, b: _fp8(a) @ _fp8(b)) if fp8 else (lambda a, b: a @ b)
    h = jax.nn.relu(mm(x, p["w1"]) + p["b1"])
    h = jax.nn.relu(mm(h, p["w2"]) + p["b2"])
    return mm(h, p["w3"]) + p["b3"]


@partial(jax.jit, static_argnames=("steps", "lr", "dtype", "fp8"))
def _local_sgd(p0, x, y, *, steps: int, lr: float, dtype: str, fp8: bool = False):
    dt = jnp.dtype(dtype)
    p = {k: v.astype(dt) for k, v in p0.items()}
    x = x.astype(dt)

    def loss(q):
        lp = jax.nn.log_softmax(_logits(q, x, fp8))
        return -jnp.mean(jnp.take_along_axis(lp, y[:, None], axis=1))

    losses = []
    start = p
    for _ in range(steps):
        value, grad = jax.value_and_grad(loss)(p)
        p = {k: p[k] - jnp.asarray(lr, dt) * grad[k] for k in p}
        losses.append(value.astype(jnp.float32))
    update = {k: (p[k] - start[k]).astype(jnp.float32) for k in p}
    return update, jnp.mean(jnp.stack(losses))


def _flat(tree):
    return jnp.concatenate([jnp.ravel(tree[k]).astype(jnp.float32) for k in sorted(tree)])


# -- the MLP kind against it ---------------------------------------------------

def _same(a, b):
    la, lb = jax.tree.leaves(a), jax.tree.leaves(b)
    assert jax.tree.structure(a) == jax.tree.structure(b)
    for x, y in zip(la, lb):
        x, y = np.asarray(x), np.asarray(y)
        assert x.dtype == y.dtype and x.shape == y.shape
        assert np.array_equal(x, y), np.max(np.abs(x.astype(np.float64) - y))


@pytest.fixture(scope="module")
def tiny():
    return S.shrunk(S.cell_spec("fedavg-mnist-2nn.m16"), nodes=48, apps=2, warm_applies=2,
                    hidden=64, shard=120)


def test_mlp_kind_draws_the_parents_weights_and_data(tiny):
    cfg, model = tiny.config, tiny.model
    dep = fixture.build(tiny, SEED)
    _same(dep.params0, init_params(SEED, model, 2))
    for a, by_worker in enumerate(dep.data):
        old = app_data(SEED, a, model, len(by_worker), int(cfg["shard"]),
                       int(cfg["label_shards"]), float(cfg["centre_scale"]))
        _same(list(by_worker.values()), old)
    # the priced payload: what the parent priced, 4 bytes a float32 weight
    assert dep.run_kwargs["model_bytes"] == 4.0 * tiny.n_params == cfg["model_bytes"]
    assert [app.model for app in dep.apps] == ["mlp", "mlp"]


@pytest.mark.parametrize("steps,dtype,fp8", [
    (1, "float32", False), (2, "float32", False), (1, "float32", True), (1, "bfloat16", False),
])
def test_reference_update_is_the_parents(tiny, steps, dtype, fp8):
    p0 = jax.tree.map(np.asarray, fixture.build(tiny, SEED).params0[1])
    x, y = app_data(SEED, 1, tiny.model, 2, 120, 2, 0.1)[0]
    with jax.default_matmul_precision("bfloat16"):
        new = reference._local_sgd(p0, (jnp.asarray(x), jnp.asarray(y)), loss=mlp.loss,
                                   steps=steps, lr=0.1, dtype=dtype, fp8=fp8)
        old = _local_sgd(p0, jnp.asarray(x), jnp.asarray(y), steps=steps, lr=0.1, dtype=dtype,
                         fp8=fp8)
    _same(new, old)
    _same(reference._flat(new[0]), _flat(old[0]))
    _same(reference._unflat(_flat(old[0]), old[0]), old[0])


# -- a kind no bench file names --------------------------------------------------

def _toy_kind() -> types.ModuleType:
    """A token model with nested weights: mean of the tokens' embeddings,
    a linear head, next-class cross-entropy.  Its inputs are integers."""
    toy = types.ModuleType("bench.models.toy_tokens")
    toy.PROGRAM = "toy"

    def shapes(model):
        v, d = int(model["vocab"]), int(model["width"])
        return {"embed/table": (v, d), "head/b": (v,), "head/w": (d, v)}

    def init_params(key_seed, model, n_apps):
        v, d = int(model["vocab"]), int(model["width"])

        @partial(jax.jit, static_argnums=(1,))
        def draw(key, n):
            out = []
            for k in jax.random.split(key, n):
                k1, k2 = jax.random.split(k)
                out.append({"embed": {"table": jax.random.normal(k1, (v, d), jnp.float32)},
                            "head": {"b": jnp.zeros((v,), jnp.float32),
                                     "w": jax.random.normal(k2, (d, v), jnp.float32) / d ** 0.5}})
            return out

        return draw(jax.random.key(key_seed), n_apps)

    def app_data(seed, app, config, workers):
        model, shard = config["model"], int(config["shard"])
        rng = np.random.default_rng(fixture.sub_seed(seed, fixture.DATA, app))
        tokens = rng.integers(0, int(model["vocab"]), (workers, shard, int(model["context"])))
        target = tokens[:, :, -1].astype(np.int32)
        return [(tokens[i].astype(np.int32), target[i]) for i in range(workers)]

    def loss(p, batch, *, mm, dtype):
        tokens, target = batch
        h = jnp.mean(p["embed"]["table"][tokens], axis=1)
        lp = jax.nn.log_softmax(mm(h, p["head"]["w"]) + p["head"]["b"])
        return -jnp.mean(jnp.take_along_axis(lp, target[:, None], axis=1))

    def train_flops(model, config):
        return 6 * int(config["shard"]) * int(model["width"]) * int(model["vocab"])

    def shrink(model, *, vocab=None):
        return {**model, **({"vocab": vocab} if vocab else {})}

    for f in (shapes, init_params, app_data, loss, train_flops, shrink):
        setattr(toy, f.__name__, f)
    return toy


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A checkout whose only cell runs the toy kind, which is importable
    as ``bench.models.toy_tokens`` but exists as no file."""
    monkeypatch.setitem(sys.modules, "bench.models.toy_tokens", _toy_kind())
    bm = S.benchmark()
    cfg = S.load_json(os.path.join(ROOT, "bench", "configs", "fedavg-mnist-2nn.json"))
    model = {"kind": "toy_tokens", "vocab": 97, "width": 24, "context": 5}
    n = 97 * 24 * 2 + 97
    cfg.update(name="toy", model=model, params=n, model_bytes=4 * n, nodes=48)
    for d in ("bench/traffic", "bench/cells", "configs"):
        (tmp_path / d).mkdir(parents=True)
    (tmp_path / "configs" / "toy.json").write_text(json.dumps(cfg))
    traffic = S.load_json(os.path.join(ROOT, "bench", "traffic", "apps16-qsgd.json"))
    (tmp_path / "bench" / "traffic" / "toy-mix.json").write_text(json.dumps(traffic))
    cell = S.load_json(os.path.join(ROOT, "bench", "cells", "fedavg-mnist-2nn.m16.json"))
    (tmp_path / "bench" / "cells" / "toy.mix.json").write_text(json.dumps(cell))
    bm.update(configs=[{"name": "toy", "file": "configs/toy.json"}],
              workloads=[{"name": "toy.mix", "config": "toy", "traffic": "toy-mix", "chips": 1}])
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bm))
    return tmp_path


def test_a_new_kind_needs_no_bench_edit(toy_root):
    spec = S.cell_spec("toy.mix", root=toy_root)
    assert spec.n_params == spec.config["params"] == 97 * 24 * 2 + 97
    spec = S.shrunk(spec, nodes=48, apps=2, warm_applies=2, shard=16, vocab=31)
    assert spec.config["params"] == 31 * 24 * 2 + 31
    assert spec.config["model_bytes"] == 4 * spec.config["params"]

    dep = fixture.build(spec, SEED)
    assert [app.model for app in dep.apps] == ["toy", "toy"]
    assert dep.run_kwargs["model_bytes"] == float(spec.config["model_bytes"])
    p0 = jax.tree.map(np.asarray, dep.params0[0])
    assert p0["embed"]["table"].shape == (31, 24)
    workers = list(dep.data[0])
    tokens, _ = dep.data[0][workers[0]]
    assert tokens.dtype == np.int32 and tokens.shape == (16, 5)

    # three applies: two fresh commits, then a stale one beside a fresh
    # one, then one from the latest version
    schedule = [[(workers[0], 0, 0), (workers[1], 0, 1)],
                [(workers[2], 1, 2), (workers[3], 0, 3)],
                [(workers[4], 2, 4)]]
    kw = dict(app=0, params0=p0, data=dep.data[0], schedule=schedule, config=spec.config,
              traffic=spec.traffic, policy_seed=dep.policy_seed)
    runs = {m: reference.follow(mode=m, **kw) for m in ("sound", "fp8", "bf16", "frozen", "half")}
    sound = runs["sound"]
    assert len(sound.params) == len(sound.held) == 3 and all(map(math.isfinite, sound.losses))
    assert jax.tree.structure(sound.params[-1]) == jax.tree.structure(p0)
    assert not np.array_equal(sound.params[0]["head"]["w"], p0["head"]["w"])
    _same(runs["frozen"].params[-1], p0)
    for m in ("fp8", "bf16", "half"):
        assert not np.array_equal(runs[m].params[-1]["head"]["w"], sound.params[-1]["head"]["w"]), m

    # the comparison reads nested weights by their paths
    ref = (sound.params, sound.losses, sound.held)
    same = compare.app_numbers(p0, ref, ref, broadcast=True)
    assert set(same) >= {"update_gap", "change_gap", "broadcast_gap"}
    assert all(v == 0.0 for v in same.values()), same
    frozen = runs["frozen"]
    off = compare.app_numbers(p0, (frozen.params, frozen.losses, frozen.held), ref, broadcast=True)
    assert off["update_gap"] == pytest.approx(1.0)
