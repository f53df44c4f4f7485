"""Checkpointing with k-replica writes (the paper's master-state replication).

Totoro+ §IV-D: "the master node in each communication round replicates the
training state across k nodes in its neighborhood set (k=2 by default)";
on master failure the takeover node restores from any replica.  Here a
"neighborhood node" is a distinct storage target (directory standing in
for a peer's disk); ``save`` fsyncs k replicas with checksums, ``restore``
reads the first intact one — so the training loop survives loss of any
k-1 replicas.

Arrays are stored as flat .npz per replica with a JSON manifest (pytree
structure + shapes + per-file SHA1).  Checkpoints hold *full logical*
arrays, so resume works onto any mesh shape (elastic re-shard): the
launcher re-device_puts with the new NamedShardings.  At 1000+ node scale
you would swap the .npz body for per-host shard files (OCDBT-style) while
keeping this manifest/replica protocol; see DESIGN.md §4.
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
import tempfile
from typing import Any

import jax
import numpy as np


def _flatten_with_paths(tree: Any):
    flat, treedef = jax.tree.flatten_with_path(tree)
    paths = ["/".join(str(getattr(k, "key", getattr(k, "idx", k))) for k in kp) for kp, _ in flat]
    leaves = [v for _, v in flat]
    return paths, leaves, treedef


def _sha1(path: str) -> str:
    h = hashlib.sha1()
    with open(path, "rb") as f:
        for chunk in iter(lambda: f.read(1 << 20), b""):
            h.update(chunk)
    return h.hexdigest()


def save(tree: Any, directory: str, *, step: int, replicas: int = 2) -> list[str]:
    """Write ``replicas`` identical copies under directory/replica_i/step_N."""
    paths, leaves, treedef = _flatten_with_paths(tree)
    arrays = [np.asarray(x) for x in leaves]
    written = []
    for r in range(replicas):
        dst = os.path.join(directory, f"replica_{r}", f"step_{step:08d}")
        tmp = dst + ".tmp"
        os.makedirs(tmp, exist_ok=True)
        npz = os.path.join(tmp, "arrays.npz")
        np.savez(npz, **{f"a{i}": a for i, a in enumerate(arrays)})
        manifest = {
            "step": step,
            "paths": paths,
            "dtypes": [str(a.dtype) for a in arrays],
            "shapes": [list(a.shape) for a in arrays],
            "sha1": _sha1(npz),
        }
        with open(os.path.join(tmp, "manifest.json"), "w") as f:
            json.dump(manifest, f)
        if os.path.exists(dst):
            shutil.rmtree(dst)
        os.replace(tmp, dst)
        written.append(dst)
    return written


def latest_step(directory: str) -> int | None:
    steps = set()
    if not os.path.isdir(directory):
        return None
    for rep in os.listdir(directory):
        rd = os.path.join(directory, rep)
        if not os.path.isdir(rd):
            continue
        for s in os.listdir(rd):
            if s.startswith("step_") and not s.endswith(".tmp"):
                steps.add(int(s[5:]))
    return max(steps) if steps else None


def restore(tree_like: Any, directory: str, *, step: int | None = None) -> tuple[Any, int]:
    """Restore from the first intact replica (checksum-verified).

    ``tree_like`` provides the pytree structure (values ignored).
    """
    if step is None:
        step = latest_step(directory)
        if step is None:
            raise FileNotFoundError(f"no checkpoints under {directory}")
    errors = []
    for rep in sorted(os.listdir(directory)):
        d = os.path.join(directory, rep, f"step_{step:08d}")
        if not os.path.isdir(d):
            continue
        try:
            with open(os.path.join(d, "manifest.json")) as f:
                manifest = json.load(f)
            npz_path = os.path.join(d, "arrays.npz")
            if _sha1(npz_path) != manifest["sha1"]:
                raise IOError(f"checksum mismatch in {d}")
            with np.load(npz_path) as z:
                arrays = [z[f"a{i}"] for i in range(len(manifest["paths"]))]
            _, leaves, treedef = _flatten_with_paths(tree_like)
            if len(leaves) != len(arrays):
                raise IOError(
                    f"leaf count mismatch: ckpt {len(arrays)} vs tree {len(leaves)}"
                )
            return jax.tree.unflatten(treedef, arrays), manifest["step"]
        except Exception as e:  # corrupted replica: try the next one
            errors.append(f"{d}: {e}")
    raise IOError("all replicas unreadable:\n" + "\n".join(errors))


def corrupt_replica(directory: str, replica: int, step: int) -> None:
    """Test helper: simulate a failed neighborhood node (truncate its copy)."""
    d = os.path.join(directory, f"replica_{replica}", f"step_{step:08d}", "arrays.npz")
    with open(d, "r+b") as f:
        f.truncate(max(0, os.path.getsize(d) // 2))
