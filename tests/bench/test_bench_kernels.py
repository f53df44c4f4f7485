"""The benchmark's cost models, peaks table and cell files: operations and
bytes at known shapes (the unpadded counts), and every name in
``BENCHMARK.json`` resolving to its files."""
import json
import os
import re
import sys

import pytest

ROOT = os.path.abspath(os.path.join(os.path.dirname(__file__), "..", ".."))
sys.path.insert(0, ROOT)

from bench.lib import spec as S  # noqa: E402

ROWS = 85_226  # a ResNet-34-sized delta on rows of 256


def _k(name):
    return S.kernel(name)


def test_tree_aggregate_counts_unpadded_rows():
    f, b = _k("tree_aggregate").cost((((ROWS, 4, 256), 4), ((ROWS, 4), 4)), {})
    assert f == 2 * ROWS * 4 * 256
    assert b == ROWS * 4 * 256 * 4 + ROWS * 4 * 4 + ROWS * 256 * 4
    # the Pallas branch pads each row to a 1,024-wide tile; that padding
    # is not work the aggregation needs
    _, padded = _k("tree_aggregate").cost((((ROWS, 4, 1024), 4), ((ROWS, 4), 4)), {})
    assert padded / b == pytest.approx(4.0, rel=3e-3)


def test_tree_aggregate_float32_apply_path():
    n = 21_817_808
    f, b = _k("tree_aggregate").cost((((1, 4, n), 4), ((1, 4), 4)), {})
    assert f == 8 * n
    assert b == 4 * n * 4 + 16 + n * 4


def test_qsgd_quantize_counts():
    f, b = _k("qsgd_quantize").cost((((ROWS, 256), 4), ((ROWS, 256), 4)), {"levels": 127})
    n = ROWS * 256
    assert f == 5 * n
    assert b == n * (4 + 4 + 1) + ROWS * 4


def test_apply_quantized_broadcast_counts():
    f, b = _k("apply_quantized_broadcast").cost(
        (((ROWS, 256), 4), ((3, ROWS, 256), 1), ((3, ROWS, 1), 4)), {})
    n = ROWS * 256
    assert f == 2 * 3 * n
    assert b == 4 * n + 3 * n + 3 * ROWS * 4 + 4 * n


def test_peaks_table_is_keyed_by_device_kind():
    v5e = S.peaks("TPU v5 lite")
    assert v5e["flops_per_s"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9
    assert "Google Cloud" in v5e["source"]
    with pytest.raises(KeyError, match="not in bench/peaks.json"):
        S.peaks("TPU v99")


NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")


def test_every_name_resolves_to_its_files():
    bm = S.benchmark()
    layers = {m["layer"] for m in bm["per_layer"]}
    assert layers == {"event core and scheduler", "FL engine", "verbs and compression",
                      "host-device transfers", "kernels", "device"}
    e2e = {m["name"] for m in bm["end_to_end"]}
    assert "setup_s" in e2e
    for c in bm["configs"]:
        cfg = S.load_json(os.path.join(ROOT, c["file"]))
        assert cfg["name"] == c["name"] and NAME.match(c["name"])
        # the priced payload is the weights at their stored dtype
        n = S.n_params(cfg["model"])
        assert cfg["params"] == n and cfg["model_bytes"] == n * S.stored_itemsize(cfg)
    for w in bm["workloads"]:
        spec = S.cell_spec(w["name"])
        assert NAME.match(w["name"]) and len(w["why"]) <= 200
        assert spec.per_layer and {"setup_s", "applies_per_s"} <= {m["name"] for m in spec.end_to_end}
        assert set(spec.cell["limits"]) >= {"loss_gap", "update_gap", "change_gap"}
    for m in bm["per_layer"]:
        assert callable(S.reader(m["name"])) and m["moves"] in e2e
        # every per-layer metric names its cells: a later cell opts in by name
        assert m["workloads"] and set(m["workloads"]) <= {w["name"] for w in bm["workloads"]}
    for m in bm["end_to_end"] + bm["per_layer"]:
        assert NAME.match(m["name"]) and m["better"] in ("lower", "higher")
    assert len(json.dumps(bm)) < 64 * 1024
