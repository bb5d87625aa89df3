"""Checkpointing: per-leaf shard files + manifest, atomic commit, async
double-buffered saves, elastic restore (reshard to any mesh), and the
TUW-tree consolidation plan (the paper's gatherv as checkpoint
infrastructure).

Layout:
  <dir>/step_<n>/manifest.json        tree structure, shapes, dtypes, step
  <dir>/step_<n>/<leaf_key>.npy       full-leaf arrays (host-assembled)
A step directory is written to <dir>/.tmp_<n> and atomically renamed —
a crash mid-save never corrupts the latest complete checkpoint.
"""
from __future__ import annotations

import json
import os
import shutil
import threading

import numpy as np

import jax

from repro.core import build_gather_tree, simulate_gather, CostParams


def _flatten(tree):
    leaves, treedef = jax.tree_util.tree_flatten_with_path(tree)
    out = {}
    for path, leaf in leaves:
        key = "/".join(str(getattr(k, "key", getattr(k, "idx", getattr(
            k, "name", k)))) for k in path)
        out[key] = leaf
    return out, treedef


def save(tree, step: int, directory: str, extra: dict | None = None) -> str:
    """Synchronous atomic save.  Returns the committed path."""
    flat, _ = _flatten(tree)
    tmp = os.path.join(directory, f".tmp_{step}")
    final = os.path.join(directory, f"step_{step:08d}")
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    manifest = {"step": step, "leaves": {}, "extra": extra or {}}
    sizes = []
    for key, leaf in flat.items():
        arr = np.asarray(leaf)
        fn = key.replace("/", "__") + ".npy"
        np.save(os.path.join(tmp, fn), arr)
        manifest["leaves"][key] = {"file": fn, "shape": list(arr.shape),
                                   "dtype": str(arr.dtype)}
        sizes.append(int(arr.nbytes))
    manifest["consolidation"] = plan_consolidation(sizes)
    with open(os.path.join(tmp, "manifest.json"), "w") as f:
        json.dump(manifest, f)
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)  # atomic commit
    return final


def plan_consolidation(shard_bytes: list[int], root: int = 0) -> dict:
    """The paper applied: plan the irregular gather of per-worker shard
    bytes to the checkpoint coordinator with the TUW tree, and report the
    linear-time cost vs the trivial direct gather (EXPERIMENTS §Perf uses
    the same numbers).  Stored in the manifest for the restore planner."""
    if not shard_bytes:
        return {}
    tree = build_gather_tree(list(shard_bytes), root=root)
    # the canonical ICI calibration, converted to microseconds so the
    # manifest's *_us keys stay honest (sizes below are in bytes)
    params = CostParams.tpu_ici().to_us()
    from repro.core.baselines import linear_tree
    direct = simulate_gather(linear_tree(list(shard_bytes), root), params)
    tuw = simulate_gather(tree, params, include_construction=True)
    return {"n_shards": len(shard_bytes),
            "total_bytes": int(sum(shard_bytes)),
            "tuw_rounds": tree.rounds,
            "tuw_us": float(tuw), "direct_us": float(direct),
            # adaptive choice, exactly the paper's guideline logic: the
            # tree wins unless startups are negligible vs the data
            "chosen": "tuw" if tuw <= direct else "direct"}


def latest_step(directory: str) -> int | None:
    """Largest step with a COMPLETE manifest (crash-safe discovery)."""
    if not os.path.isdir(directory):
        return None
    best = None
    for name in os.listdir(directory):
        if not name.startswith("step_"):
            continue
        if not os.path.exists(os.path.join(directory, name, "manifest.json")):
            continue
        try:
            s = int(name.split("_")[1])
        except ValueError:
            continue
        best = s if best is None else max(best, s)
    return best


def restore_latest(template, directory: str, shardings=None):
    """Elastic resume entry point: restore the newest COMPLETE step.

    Returns ``(tree, manifest)`` or ``(template, None)`` when no complete
    checkpoint exists.  The shrink path restores through this with the
    SURVIVORS' shardings tree — the checkpoint layout is full-leaf
    host arrays, so resharding onto a p-1 mesh is just a different
    ``shardings`` argument, no rewrite of the checkpoint."""
    step = latest_step(directory)
    if step is None:
        return template, None
    return restore(template, step, directory, shardings=shardings)


def shrink_consolidation(shard_bytes: list[int], lost_ranks,
                         root: int = 0) -> dict:
    """Re-plan checkpoint consolidation after an elastic shrink.

    Drops the lost ranks' shard entries, remaps ``root`` onto the
    survivor numbering (a dead coordinator falls back to survivor 0),
    and returns :func:`plan_consolidation` of the surviving shards plus
    the rank remap — the gather tree is rebuilt over p-1 ranks, not
    patched, exactly like the collective plans after an evict."""
    lost = {int(r) for r in (lost_ranks or ())}
    survivors = [r for r in range(len(shard_bytes)) if r not in lost]
    if not survivors:
        raise ValueError("no surviving ranks")
    if root in lost:
        root = survivors[0]
    plan = plan_consolidation([shard_bytes[r] for r in survivors],
                              root=survivors.index(root))
    plan["survivors"] = survivors
    plan["rank_remap"] = {old: new for new, old in enumerate(survivors)}
    plan["root"] = int(root)
    return plan


def restore(template, step: int, directory: str, shardings=None):
    """Restore into ``template``'s tree structure.  ``shardings`` (same
    tree of NamedSharding/None) reshards on load — elastic restore onto a
    different mesh is just a different shardings tree."""
    path = os.path.join(directory, f"step_{step:08d}")
    with open(os.path.join(path, "manifest.json")) as f:
        manifest = json.load(f)
    flat_t, treedef = _flatten(template)
    flat_s, _ = _flatten(shardings) if shardings is not None else ({}, None)
    leaves = []
    for key, leaf in flat_t.items():
        meta = manifest["leaves"][key]
        arr = np.load(os.path.join(path, meta["file"]))
        expect = tuple(np.asarray(leaf).shape) if hasattr(leaf, "shape") \
            else ()
        assert tuple(arr.shape) == tuple(meta["shape"]), key
        if expect and tuple(arr.shape) != expect:
            raise ValueError(f"shape mismatch for {key}: ckpt {arr.shape} "
                             f"vs template {expect}")
        sh = flat_s.get(key)
        leaves.append(jax.device_put(arr, sh) if sh is not None
                      else jax.device_put(arr))
    return jax.tree_util.tree_unflatten(treedef, leaves), manifest


class AsyncCheckpointer:
    """Double-buffered background saves: snapshot to host synchronously
    (cheap), write in a thread.  ``wait()`` joins before the next save or
    at shutdown — one in-flight save max, like production checkpointers."""

    def __init__(self, directory: str):
        self.directory = directory
        self._thread: threading.Thread | None = None
        self.last_path: str | None = None
        self._err: Exception | None = None

    def save(self, tree, step: int, extra: dict | None = None):
        self.wait()
        host_tree = jax.tree.map(lambda x: np.asarray(x), tree)

        def work():
            try:
                self.last_path = save(host_tree, step, self.directory, extra)
            except Exception as e:  # pragma: no cover
                self._err = e
        self._thread = threading.Thread(target=work, daemon=True)
        self._thread.start()

    def wait(self):
        if self._thread is not None:
            self._thread.join()
            self._thread = None
        if self._err is not None:
            raise self._err
