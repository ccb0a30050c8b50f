"""Sharded, atomic, async checkpointing with elastic restore.

Design points for 1000+ nodes (DESIGN.md §5):

- **Sharded writes**: every host writes only the *addressable* shards of
  each array, one ``<leaf>.<shard_index>.npy`` file per distinct shard
  (replicated shards are written once, by the lowest-index owner).  No
  host ever materializes a full array.
- **Atomicity**: a checkpoint is staged into ``step_<N>.tmp`` and
  ``os.rename``d to ``step_<N>`` only after every shard file and the
  manifest are durable — a crashed writer leaves no half checkpoint, and
  restore only ever sees complete directories.
- **Async**: ``save(..., block=False)`` snapshots device arrays to host
  (the only synchronous part) and hands the serialization to a background
  thread, overlapping I/O with the next training steps.
- **Elastic restore**: ``restore`` takes *target* shardings that may come
  from a different mesh than the save-time mesh.  Shard files are memmap'd
  and each target shard reads exactly the slice it needs
  (``make_array_from_callback``) — restoring a 512-chip checkpoint onto a
  256-chip mesh (or CPU) touches each byte once.
- **Pipeline state**: the data pipeline is a pure function of (seed, step,
  host), so the manifest's ``step`` *is* the full pipeline state.
"""
from __future__ import annotations

import json
import os
import shutil
import threading
from typing import Any, Dict, List, Optional, Tuple

import jax
import numpy as np


def _path_str(path) -> str:
    parts = []
    for k in path:
        if hasattr(k, "key"):
            parts.append(str(k.key))
        elif hasattr(k, "idx"):
            parts.append(str(k.idx))
        elif hasattr(k, "name"):
            parts.append(str(k.name))
        else:
            parts.append(str(k))
    return ".".join(parts)


def _leaf_files(leaf: Any) -> List[Tuple[str, Tuple[slice, ...], np.ndarray]]:
    """[(shard_suffix, index, host_array)] for the addressable shards this
    process must write (dedup replicated shards by device order)."""
    if not isinstance(leaf, jax.Array) or not hasattr(leaf, "addressable_shards"):
        return [("s0", (), np.asarray(leaf))]
    seen = set()
    out = []
    for shard in leaf.addressable_shards:
        key = tuple((s.start, s.stop) for s in
                    _norm_index(shard.index, leaf.shape))
        if key in seen:
            continue  # replica of a shard another device already owns
        seen.add(key)
        out.append((f"s{len(out)}", _norm_index(shard.index, leaf.shape),
                    np.asarray(shard.data)))
    return out


def _norm_index(index, shape) -> Tuple[slice, ...]:
    norm = []
    for s, dim in zip(index, shape):
        start = 0 if s.start is None else int(s.start)
        stop = dim if s.stop is None else int(s.stop)
        norm.append(slice(start, stop))
    return tuple(norm)


class CheckpointManager:
    def __init__(self, directory: str, *, keep: int = 3):
        self.directory = directory
        self.keep = keep
        os.makedirs(directory, exist_ok=True)
        self._pending: Optional[threading.Thread] = None
        self._failure: Optional[BaseException] = None

    # ------------------------------------------------------------------ #
    def save(self, step: int, tree: Any, *, block: bool = True,
             extra_meta: Optional[dict] = None) -> str:
        """Checkpoint a pytree of (possibly sharded) arrays."""
        self.wait()  # only one async save in flight
        flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
        # synchronous part: snapshot device -> host
        records = []
        for path, leaf in flat:
            name = _path_str(path)
            shards = _leaf_files(leaf)
            dtype = str(shards[0][2].dtype)
            shape = list(leaf.shape) if hasattr(leaf, "shape") else []
            records.append((name, shape, dtype, shards))

        final = os.path.join(self.directory, f"step_{step:08d}")
        tmp = final + ".tmp"

        def write():
            if os.path.exists(tmp):
                shutil.rmtree(tmp)
            os.makedirs(tmp)
            manifest = {"step": step, "leaves": [],
                        "extra": extra_meta or {}}
            for name, shape, dtype, shards in records:
                entry = {"name": name, "shape": shape, "dtype": dtype,
                         "shards": []}
                for suffix, index, arr in shards:
                    fname = f"{name}.{suffix}.npy"
                    np.save(os.path.join(tmp, fname), arr)
                    entry["shards"].append({
                        "file": fname,
                        "index": [[s.start, s.stop] for s in index],
                    })
                manifest["leaves"].append(entry)
            with open(os.path.join(tmp, "manifest.json"), "w") as f:
                json.dump(manifest, f)
            if os.path.exists(final):
                shutil.rmtree(final)
            os.rename(tmp, final)          # atomic publish
            self._gc()

        if block:
            write()
        else:
            def write_async():
                try:
                    write()
                except BaseException as exc:
                    self._failure = exc     # raised by wait()
                    raise
            self._pending = threading.Thread(target=write_async, daemon=True)
            self._pending.start()
        return final

    def wait(self):
        """Join the save in flight; raise if it failed."""
        if self._pending is not None:
            self._pending.join()
            self._pending = None
        failure, self._failure = self._failure, None
        if failure is not None:
            raise RuntimeError("asynchronous checkpoint save failed") \
                from failure

    def _gc(self):
        steps = self.all_steps()
        for s in steps[:-self.keep] if self.keep else []:
            shutil.rmtree(os.path.join(self.directory, f"step_{s:08d}"),
                          ignore_errors=True)

    # ------------------------------------------------------------------ #
    def all_steps(self) -> List[int]:
        out = []
        for d in os.listdir(self.directory):
            if d.startswith("step_") and not d.endswith(".tmp"):
                out.append(int(d[5:]))
        return sorted(out)

    def latest_step(self) -> Optional[int]:
        steps = self.all_steps()
        return steps[-1] if steps else None

    # ------------------------------------------------------------------ #
    def restore(self, tree_like: Any, *, step: Optional[int] = None,
                shardings: Any = None) -> Tuple[int, Any]:
        """Restore onto (possibly different) target shardings.

        ``tree_like``: pytree of arrays or ShapeDtypeStructs giving the
        target structure.  ``shardings``: matching pytree of Sharding (or
        None -> host-local numpy arrays).  Returns (step, restored tree).
        """
        self.wait()
        if step is None:
            step = self.latest_step()
        if step is None:
            raise FileNotFoundError(f"no checkpoint in {self.directory}")
        d = os.path.join(self.directory, f"step_{step:08d}")
        with open(os.path.join(d, "manifest.json")) as f:
            manifest = json.load(f)
        by_name = {e["name"]: e for e in manifest["leaves"]}

        flat, treedef = jax.tree_util.tree_flatten_with_path(tree_like)
        sh_flat = (jax.tree_util.tree_leaves(shardings)
                   if shardings is not None else [None] * len(flat))
        assert len(sh_flat) == len(flat)
        out = []
        for (path, leaf), sharding in zip(flat, sh_flat):
            name = _path_str(path)
            entry = by_name[name]
            shape = tuple(entry["shape"])
            dtype = np.dtype(entry["dtype"])
            mmaps = [(tuple(slice(a, b) for a, b in s["index"]),
                      np.load(os.path.join(d, s["file"]), mmap_mode="r"))
                     for s in entry["shards"]]

            def read_slice(index, shape=shape, dtype=dtype, mmaps=mmaps):
                index = _norm_index(index, shape)
                if not shape:
                    return np.asarray(mmaps[0][1])
                buf = np.empty([s.stop - s.start for s in index], dtype)
                for src_index, arr in mmaps:
                    inter = []
                    for tgt, src in zip(index, src_index):
                        lo = max(tgt.start, src.start)
                        hi = min(tgt.stop, src.stop)
                        if lo >= hi:
                            break
                        inter.append((lo, hi, tgt.start, src.start))
                    else:
                        dst_idx = tuple(slice(lo - t0, hi - t0)
                                        for lo, hi, t0, _ in inter)
                        src_idx = tuple(slice(lo - s0, hi - s0)
                                        for lo, hi, _, s0 in inter)
                        buf[dst_idx] = arr[src_idx]
                return buf

            if sharding is None:
                out.append(read_slice(tuple(slice(None) for _ in shape)))
            else:
                out.append(jax.make_array_from_callback(
                    shape, sharding,
                    lambda idx, rs=read_slice: rs(idx)))
        return step, jax.tree_util.tree_unflatten(treedef, out)
