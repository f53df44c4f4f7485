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


def _one_cell_root(root, model: dict, params: int):
    """``root`` made a checkout whose only cell, ``toy.mix``, runs
    ``model`` (``params`` trained weights, 4 bytes each) on the m16 mix."""
    bm = S.benchmark()
    cfg = S.load_json(os.path.join(ROOT, "bench", "configs", "fedavg-mnist-2nn.json"))
    cfg.update(name="toy", model=model, params=params, model_bytes=4 * params, nodes=48)
    for d in ("bench/traffic", "bench/cells", "configs"):
        (root / d).mkdir(parents=True)
    (root / "configs" / "toy.json").write_text(json.dumps(cfg))
    traffic = S.load_json(os.path.join(ROOT, "bench", "traffic", "apps16-qsgd.json"))
    (root / "bench" / "traffic" / "toy-mix.json").write_text(json.dumps(traffic))
    cell = S.load_json(os.path.join(ROOT, "bench", "cells", "fedavg-mnist-2nn.m16.json"))
    (root / "bench" / "cells" / "toy.mix.json").write_text(json.dumps(cell))
    bm.update(configs=[{"name": "toy", "file": "configs/toy.json"}],
              workloads=[{"name": "toy.mix", "config": "toy", "traffic": "toy-mix", "chips": 1}])
    (root / "BENCHMARK.json").write_text(json.dumps(bm))
    return root


@pytest.fixture
def toy_root(tmp_path, monkeypatch):
    """A checkout whose only cell runs the toy kind, which is importable
    as ``bench.models.toy_tokens`` but exists as no file."""
    monkeypatch.setitem(sys.modules, "bench.models.toy_tokens", _toy_kind())
    model = {"kind": "toy_tokens", "vocab": 97, "width": 24, "context": 5}
    return _one_cell_root(tmp_path, model, 97 * 24 * 2 + 97)


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


# -- a kind with a frozen base that every app shares -----------------------------

def _frozen_base_kind(calls: dict) -> types.ModuleType:
    """A token model over a frozen embedding table that every app shares,
    stored in bfloat16 as a served base is, under a trainable head: the
    mean of the tokens' embeddings (a matmul of their counts with the
    table), a linear head, next-class cross-entropy.  ``calls`` records
    each draw of the base and each ``program_fields`` call."""
    toy = types.ModuleType("bench.models.toy_frozen")
    toy.PROGRAM = "toy"

    def shapes(model):
        v, d = int(model["vocab"]), int(model["width"])
        return {"head/b": (v,), "head/w": (d, v)}

    def init_params(key_seed, model, n_apps):
        v, d = int(model["vocab"]), int(model["width"])

        @partial(jax.jit, static_argnums=(1,))
        def draw(key, n):
            return [{"head": {"b": jnp.zeros((v,), jnp.float32),
                              "w": jax.random.normal(k, (d, v), jnp.float32) / d ** 0.5}}
                    for k in jax.random.split(key, n)]

        return draw(jax.random.key(key_seed), n_apps)

    def shared(key_seed, model):
        v, d = int(model["vocab"]), int(model["width"])
        table = jax.jit(lambda key: jax.random.normal(key, (v, d), jnp.float32).astype(
            jnp.dtype(model["base_dtype"])))(jax.random.key(key_seed))
        base = {"embed": {"table": table}}
        calls["shared"].append(base)
        return base

    def program_fields(base):
        calls["fields"].append(base)
        return {"frozen": base}

    def loss(p, batch, *, mm, dtype, shared):
        tokens, target = batch
        table = shared["embed"]["table"].astype(dtype)
        counts = jnp.mean(jax.nn.one_hot(tokens, table.shape[0], dtype=dtype), axis=1)
        h = mm(counts, table)
        lp = jax.nn.log_softmax(mm(h, p["head"]["w"]) + p["head"]["b"])
        return -jnp.mean(jnp.take_along_axis(lp, target[:, None], axis=1))

    def train_flops(model, config):
        v, d = int(model["vocab"]), int(model["width"])
        return 6 * int(config["shard"]) * d * v

    def shrink(model, *, vocab=None):
        return {**model, **({"vocab": vocab} if vocab else {})}

    toy.app_data = _toy_kind().app_data
    for f in (shapes, init_params, shared, program_fields, loss, train_flops, shrink):
        setattr(toy, f.__name__, f)
    return toy


@pytest.fixture
def frozen_root(tmp_path, monkeypatch):
    """A checkout whose only cell runs the frozen-base toy kind, and a
    program whose ``FLApp`` takes the field the kind hands the base in.
    Yields the root and the kind's record of calls."""
    from dataclasses import dataclass

    from repro.fl import rounds

    @dataclass
    class FrozenFLApp(rounds.FLApp):
        frozen: object = None

    monkeypatch.setattr(rounds, "FLApp", FrozenFLApp)
    calls = {"shared": [], "fields": []}
    monkeypatch.setitem(sys.modules, "bench.models.toy_frozen", _frozen_base_kind(calls))
    model = {"kind": "toy_frozen", "vocab": 97, "width": 24, "context": 5,
             "base_dtype": "bfloat16"}
    return _one_cell_root(tmp_path, model, 97 * 24 + 97), calls


def _paths(tree) -> set[str]:
    """The leaves of ``tree`` by their path, as ``shapes`` names them."""
    return {"/".join(str(k.key) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(tree)[0]}


def _frozen_deployment(frozen_root):
    root, calls = frozen_root
    spec = S.shrunk(S.cell_spec("toy.mix", root=root), nodes=48, apps=2, warm_applies=2,
                    shard=16, vocab=31)
    return spec, fixture.build(spec, SEED), calls


def _three_applies(workers):
    """Two fresh commits; a stale one beside a fresh one; one from the latest version."""
    return [[(workers[0], 0, 0), (workers[1], 0, 1)],
            [(workers[2], 1, 2), (workers[3], 0, 3)],
            [(workers[4], 2, 4)]]


def test_frozen_base_is_drawn_once_and_reaches_every_app(frozen_root):
    spec, dep, calls = _frozen_deployment(frozen_root)
    assert len(calls["shared"]) == 1 and calls["shared"][0] is dep.shared
    assert len(calls["fields"]) == 1 and calls["fields"][0] is dep.shared
    assert len(dep.apps) == 2 and all(app.frozen is dep.shared for app in dep.apps)
    table = dep.shared["embed"]["table"]
    assert isinstance(table, jax.Array) and table.dtype == jnp.bfloat16
    assert table.shape == (31, 24)
    # only the trained leaves are priced and carried: the base is on every node
    kind = spec.kind
    assert spec.n_params == 31 * 24 + 31
    trained = sum(math.prod(s) for s in kind.shapes(spec.model).values())
    assert spec.config["model_bytes"] == 4 * trained
    assert dep.run_kwargs["model_bytes"] == float(spec.config["model_bytes"])
    assert all(_paths(p) == set(kind.shapes(spec.model)) for p in dep.params0)
    # the same seed draws the same base and weights
    again = fixture.build(spec, SEED)
    assert np.array_equal(np.asarray(again.shared["embed"]["table"], np.float32),
                          np.asarray(table, np.float32))
    _same(again.params0, dep.params0)


@pytest.mark.parametrize("mode", reference.MODES)
def test_reference_follows_a_frozen_base_app_in_every_mode(frozen_root, mode):
    spec, dep, calls = _frozen_deployment(frozen_root)
    keys = set(spec.kind.shapes(spec.model))
    p0 = jax.tree.map(np.asarray, dep.params0[0])
    kw = dict(app=0, params0=p0, data=dep.data[0], schedule=_three_applies(list(dep.data[0])),
              config=spec.config, traffic=spec.traffic, policy_seed=dep.policy_seed,
              shared=dep.shared)
    sound = reference.follow(mode="sound", **kw)
    run = reference.follow(mode=mode, **kw)
    assert len(run.params) == len(run.held) == 3 and all(map(math.isfinite, run.losses))
    for tree in run.params + run.held:
        assert _paths(tree) == keys
    assert len(calls["shared"]) == 1  # following draws nothing
    if mode == "sound":
        assert not np.array_equal(run.params[0]["head"]["w"], p0["head"]["w"])
    elif mode == "frozen":
        _same(run.params[-1], p0)
    else:
        assert not np.array_equal(run.params[-1]["head"]["w"], sound.params[-1]["head"]["w"])


def test_frozen_base_is_an_argument_not_a_constant(frozen_root):
    """The update has the trained leaves alone; another base of the same
    shape changes the loss and reuses the compiled step."""
    spec, dep, _ = _frozen_deployment(frozen_root)
    p0 = jax.tree.map(jnp.asarray, dep.params0[1])
    batch = jax.tree.map(jnp.asarray, next(iter(dep.data[1].values())))
    table = dep.shared["embed"]["table"]
    other = {"embed": {"table": jax.random.normal(jax.random.key(7), table.shape,
                                                  jnp.float32).astype(table.dtype)}}
    kw = dict(loss=spec.kind.loss, steps=1, lr=0.1, dtype="float32")
    before = reference._local_sgd._cache_size()
    upd, loss = reference._local_sgd(p0, batch, dep.shared, **kw)
    compiled = reference._local_sgd._cache_size()
    upd2, loss2 = reference._local_sgd(p0, batch, other, **kw)
    assert compiled == before + 1 and reference._local_sgd._cache_size() == compiled
    assert _paths(upd) == _paths(upd2) == set(spec.kind.shapes(spec.model))
    assert float(loss) != float(loss2)


def test_replay_hands_the_one_base_to_every_follow(frozen_root, monkeypatch):
    """The harness's replay gives every followed app, in every mode, the
    deployment's one copy of the base."""
    from bench.lib import harness

    spec, dep, _ = _frozen_deployment(frozen_root)
    follow = [0, 1]
    replay = dict(follow=follow,
                  schedule={a: _three_applies(list(dep.data[a])) for a in follow},
                  params0={a: jax.tree.map(np.asarray, dep.params0[a]) for a in follow},
                  data={a: dep.data[a] for a in follow}, policy_seed=dep.policy_seed,
                  shared=dep.shared)
    seen = []
    orig = reference.follow

    def follow_(**kw):
        seen.append((kw["mode"], kw["shared"]))
        return orig(**kw)

    monkeypatch.setattr(reference, "follow", follow_)
    got = harness.readings(spec, replay, None, ["fp8", "frozen"])
    assert [m for m, _ in seen] == ["sound", "fp8", "frozen"] * 2
    assert all(s is dep.shared for _, s in seen)
    assert got["frozen"]["update_gap"] == pytest.approx(1.0)
    assert 0.0 < got["fp8"]["update_gap"] < 1.0


def test_a_kind_without_shared_gets_the_old_loss_arguments(tiny, monkeypatch):
    """The MLP kind's ``loss`` is called with ``mm`` and ``dtype`` alone."""
    seen = []
    orig = mlp.loss

    def loss(p, batch, **kw):
        seen.append(sorted(kw))
        return orig(p, batch, **kw)

    monkeypatch.setattr(mlp, "loss", loss)
    dep = fixture.build(tiny, SEED)
    assert dep.shared is None
    workers = list(dep.data[0])
    reference.follow(app=0, params0=jax.tree.map(np.asarray, dep.params0[0]), data=dep.data[0],
                     schedule=[[(workers[0], 0, 0)]], config=tiny.config, traffic=tiny.traffic,
                     policy_seed=dep.policy_seed)
    assert seen and all(k == ["dtype", "mm"] for k in seen)
