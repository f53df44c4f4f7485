"""Vectorized round engine + hierarchical aggregation + event simulator."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest
from oracles import reference_fused_local_training, reference_local_training

from repro import data as data_mod
from repro.core.api import TotoroSystem
from repro.core.sim import MultiAppSimulator, per_app_round_ms
from repro.fl import engine, rounds
from repro.kernels import ops as kops


def build_app(n_nodes=150, workers=8, *, ragged=True, seed=0):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2)) for i in range(n_nodes)]
    x, y = data_mod.synthetic_classification(workers * 150, 16, 4, seed=seed)
    if ragged:
        parts = data_mod.dirichlet_partition(y, workers, alpha=1.0, seed=seed + 1)
        parts = [p if len(p) else np.arange(3) for p in parts]
    else:
        parts = [np.arange(i * 150, (i + 1) * 150) for i in range(workers)]
    ws = [int(w) for w in rng.choice(nodes, size=workers, replace=False)]
    app = rounds.make_app(
        sys_, "eng-test", workers=ws,
        data_by_worker={w: (x[parts[i]], y[parts[i]]) for i, w in enumerate(ws)},
        dim=16, num_classes=4, local_steps=3, lr=0.2,
    )
    return sys_, app, (x, y)


def test_vectorized_matches_reference_loop():
    """vmapped masked local training == per-worker loop (ragged shards)."""
    _, app, _ = build_app(ragged=True)
    ws = [w for w in sorted(app.handle.tree.members) if w in app.data]
    [(d_v, w_v, l_v)] = engine.fused_local_training([(app, ws, None)])
    d_r, w_r, l_r = reference_local_training(app, ws)
    assert w_v == w_r
    np.testing.assert_allclose(l_v, l_r, rtol=1e-4, atol=1e-6)
    for a, b in zip(d_v, d_r):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=5e-4, atol=5e-6
            )


def test_vectorized_matches_reference_with_fedprox():
    _, app, _ = build_app(ragged=True, seed=3)
    app.mu = 0.1
    ws = [w for w in sorted(app.handle.tree.members) if w in app.data]
    [(d_v, _, _)] = engine.fused_local_training([(app, ws, None)])
    d_r, _, _ = reference_local_training(app, ws)
    for a, b in zip(d_v, d_r):
        for la, lb in zip(jax.tree.leaves(a), jax.tree.leaves(b)):
            np.testing.assert_allclose(
                np.asarray(la), np.asarray(lb), rtol=5e-4, atol=5e-6
            )


def test_round_vectorized_and_reference_converge_identically(monkeypatch):
    sys_v, app_v, (x, y) = build_app(seed=1)
    sys_r, app_r, _ = build_app(seed=1)
    for _ in range(3):
        rounds.run_round(sys_v, app_v)
    monkeypatch.setattr(engine, "fused_local_training", reference_fused_local_training)
    for _ in range(3):
        rounds.run_round(sys_r, app_r)
    for la, lb in zip(jax.tree.leaves(app_v.params), jax.tree.leaves(app_r.params)):
        np.testing.assert_allclose(np.asarray(la), np.asarray(lb), rtol=1e-3, atol=1e-5)
    assert rounds.evaluate(app_v, x[:300], y[:300]) > 0.7


def test_aggregation_schedule_invariants():
    sys_, app, _ = build_app(n_nodes=300, workers=32)
    tree = app.handle.tree
    sched = tree.aggregation_schedule()
    parents = [p for level in sched for p, _ in level]
    assert len(parents) == len(set(parents))  # each parent exactly once
    assert set(parents) == {n for n, c in tree.children.items() if c}
    # each level's parents share one depth; levels run deepest-first
    level_depths = [{tree.depth_of(p) for p, _ in level} for level in sched]
    assert all(len(d) == 1 for d in level_depths)
    flat_depths = [d.copy().pop() for d in level_depths]
    assert flat_depths == sorted(flat_depths, reverse=True)
    for level in sched:
        for p, kids in level:
            assert kids == sorted(tree.children[p])
            for c in kids:
                assert tree.parent[c] == p


def test_hierarchical_aggregate_matches_flat_mean():
    sys_, app, _ = build_app(n_nodes=300, workers=24, seed=2)
    tree = app.handle.tree
    rng = np.random.default_rng(0)
    members = sorted(tree.members)
    objs = {
        w: {"a": rng.standard_normal((7, 5)).astype(np.float32),
            "b": rng.standard_normal(33).astype(np.float32)}
        for w in members
    }
    wts = {w: float(rng.integers(1, 9)) for w in members}
    hier = sys_.Aggregate(app.handle.app_id, objs, weights=wts)
    flat = sys_.Aggregate(app.handle.app_id, objs, weights=wts, hierarchical=False)
    for la, lb in zip(jax.tree.leaves(hier["result"]), jax.tree.leaves(flat["result"])):
        np.testing.assert_allclose(
            np.asarray(la, np.float64), np.asarray(lb, np.float64), rtol=1e-5, atol=1e-6
        )
    # metrics follow the tree: one entry per level, traffic = edges * vec
    assert hier["levels"], "level metrics missing"
    assert hier["bytes"] == sum(lv["bytes"] for lv in hier["levels"])
    assert hier["time_ms"] == sum(lv["time_ms"] for lv in hier["levels"])
    n_edge_transfers = hier["bytes"] / (4.0 * (7 * 5 + 33))
    assert n_edge_transfers >= len(members)  # every member's update crossed >=1 edge


def test_hierarchical_aggregate_root_only_payload_weighted():
    """A weighted payload from just the root of a childless tree must
    still come back as the weighted mean (== the payload itself)."""
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=6)
    rng = np.random.default_rng(6)
    for i in range(50):
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 10, 2))
    h = sys_.CreateTree("root-only")
    v = np.ones(4, np.float32)
    res = sys_.Aggregate(h.app_id, {h.tree.root: v}, weights={h.tree.root: 2.0})
    np.testing.assert_allclose(np.asarray(res["result"]), v)


def test_hierarchical_aggregate_no_kernel_reference_path():
    sys_, app, _ = build_app(n_nodes=200, workers=10, seed=4)
    members = sorted(app.handle.tree.members)
    rng = np.random.default_rng(1)
    objs = {w: rng.standard_normal(50).astype(np.float32) for w in members}
    k = sys_.Aggregate(app.handle.app_id, objs)
    nk = sys_.Aggregate(app.handle.app_id, objs, use_kernel=False)
    np.testing.assert_allclose(np.asarray(k["result"]), np.asarray(nk["result"]), rtol=1e-5)


def test_tree_aggregate_groups_kernel_matches_oracle():
    key = jax.random.key(0)
    G, C, L = 5, 6, 700  # L not a tile multiple: wrapper pads
    g = jax.random.normal(key, (G, C, L), jnp.float32)
    w = jax.random.uniform(jax.random.fold_in(key, 1), (G, C), jnp.float32)
    w = w.at[:, -2:].set(0.0)  # ragged groups = zero-weight padding slots
    out = kops.tree_aggregate_groups(g, w)
    oracle = (np.asarray(g) * np.asarray(w)[..., None]).sum(axis=1)
    np.testing.assert_allclose(np.asarray(out), oracle, rtol=1e-5, atol=1e-5)


def build_sim_system(m_apps=3, seed=9):
    sys_ = TotoroSystem(zone_bits=2, suffix_bits=20, seed=seed)
    rng = np.random.default_rng(seed)
    nodes = [
        sys_.Join("n", i, site=i % 4, coord=rng.uniform(0, 50, 2),
                  bandwidth=float(rng.uniform(20, 100)))
        for i in range(300)
    ]
    handles = []
    for a in range(m_apps):
        h = sys_.CreateTree(f"sim-{a}")
        for w in rng.choice(nodes, size=24, replace=False):
            sys_.Subscribe(h.app_id, int(w))
        handles.append(h)
    return sys_, handles


def test_event_clock_deterministic_m3():
    sys_, handles = build_sim_system(m_apps=3)
    runs = [
        MultiAppSimulator(sys_, handles, model_bytes=1e5, compute_ms=25.0).run(rounds=3)
        for _ in range(2)
    ]
    assert runs[0] == runs[1]  # identical event traces for a fixed system
    per_app = per_app_round_ms(runs[0])
    assert len(per_app) == 3 and all(len(v) == 3 for v in per_app.values())
    assert all(t > 0 for v in per_app.values() for t in v)
    # rounds of one app complete in order
    for h in handles:
        evs = [e for e in runs[0] if e.app_id == h.app_id]
        assert [e.round for e in evs] == [0, 1, 2]
        assert all(a.end_ms <= b.end_ms for a, b in zip(evs, evs[1:]))


def test_contention_slows_shared_overlay():
    """An app's rounds are no faster with 3 concurrent apps than alone."""
    sys_, handles = build_sim_system(m_apps=3)
    alone = MultiAppSimulator(sys_, handles[:1], model_bytes=1e5, compute_ms=25.0).run(rounds=2)
    together = MultiAppSimulator(sys_, handles, model_bytes=1e5, compute_ms=25.0).run(rounds=2)
    a = np.mean(per_app_round_ms(alone)[handles[0].app_id])
    t = np.mean(per_app_round_ms(together)[handles[0].app_id])
    assert t >= a - 1e-9
