"""Fault-tolerant checkpointing: atomic, durable, resumable, mesh-elastic.

* atomic: write to ``<dir>.tmp`` then ``os.replace`` (a crashed writer never
  corrupts the last good step);
* durable: every staged file is fsynced, and so are the staging directory
  and the parent directory around the ``os.replace`` — the rename is not
  just atomic against a crashed *writer* but persistent across power loss
  (an un-fsynced rename can legally vanish on journal replay);
* verified: ``meta.json`` carries a sha256 per payload file, so a torn or
  bit-rotted checkpoint is *detected* at restore time (``verify``) instead
  of loading garbage — callers fall back to the previous step
  (``latest_valid_step`` / ``valid_steps``);
* resumable: latest-step discovery + data-cursor restore;
* elastic: ``restore`` re-device_puts every leaf under the *current* mesh's
  shardings, so a job can come back on a different topology (node failures,
  pod resize) — the "elastic scaling" leg of the fault-tolerance story.

Tree paths are percent-encoded per component before joining with ``/``, so
``("a/b",)`` and ``("a", "b")`` can never alias one another in the archive
(the un-escaped join used to collide them).
"""
from __future__ import annotations

import hashlib
import json
import os
import shutil
from typing import Any, Dict, List, Optional
from urllib.parse import quote

import jax
import numpy as np

CKPT_FORMAT_VERSION = 2   # bumped when the on-disk layout changes


def _path_key(path) -> str:
    """Collision-proof archive key for one tree path.

    Each component is percent-encoded (``/`` and ``%`` escaped) before the
    ``/`` join, so distinct paths always produce distinct keys — a raw
    join would alias ``("a/b",)`` with ``("a", "b")``.
    """
    return "/".join(
        quote(str(getattr(p, "key", getattr(p, "idx", p))), safe="")
        for p in path)


def _flatten(tree) -> Dict[str, Any]:
    flat, _ = jax.tree_util.tree_flatten_with_path(tree)
    return {_path_key(path): leaf for path, leaf in flat}


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


def _fsync_path(path: str) -> None:
    """fsync a file or directory (directory fsync persists the entry list,
    which is what makes a rename durable on power loss)."""
    fd = os.open(path, os.O_RDONLY)
    try:
        os.fsync(fd)
    finally:
        os.close(fd)


def _write_file(path: str, data: bytes) -> None:
    with open(path, "wb") as f:
        f.write(data)
        f.flush()
        os.fsync(f.fileno())


def save(ckpt_dir: str, step: int, tree, extra: Optional[dict] = None,
         blobs: Optional[Dict[str, bytes]] = None) -> str:
    """Write one atomic, durable checkpoint at ``<ckpt_dir>/step_<n>``.

    ``blobs`` are opaque byte payloads stored alongside the array archive
    (host-side closures — label maps, cursors — that are not jax trees);
    each is checksummed in ``meta`` exactly like ``arrays.npz`` and read
    back with :func:`load_blob`.
    """
    os.makedirs(ckpt_dir, exist_ok=True)
    final = os.path.join(ckpt_dir, f"step_{step:08d}")
    tmp = final + ".tmp"
    if os.path.exists(tmp):
        shutil.rmtree(tmp)
    os.makedirs(tmp, exist_ok=True)
    flat = _flatten(tree)
    arrays = {k: np.asarray(v) for k, v in flat.items()}
    np.savez(os.path.join(tmp, "arrays.npz"), **arrays)
    _fsync_path(os.path.join(tmp, "arrays.npz"))
    checksums = {"arrays.npz": _sha256(os.path.join(tmp, "arrays.npz"))}
    for name, data in (blobs or {}).items():
        assert name not in ("arrays.npz", "meta.json"), name
        _write_file(os.path.join(tmp, name), data)
        checksums[name] = _sha256(os.path.join(tmp, name))
    meta = {"step": step, "keys": sorted(arrays),
            "format_version": CKPT_FORMAT_VERSION,
            "checksums": checksums,
            "extra": extra or {}}
    _write_file(os.path.join(tmp, "meta.json"),
                json.dumps(meta).encode("utf-8"))
    _fsync_path(tmp)                       # staged entries are on disk
    if os.path.exists(final):
        shutil.rmtree(final)
    os.replace(tmp, final)
    _fsync_path(ckpt_dir)                  # the rename itself is durable
    return final


def checkpoint_steps(ckpt_dir: str) -> List[int]:
    """All step numbers with a (not necessarily valid) final directory."""
    if not os.path.isdir(ckpt_dir):
        return []
    return sorted(int(d.split("_")[1]) for d in os.listdir(ckpt_dir)
                  if d.startswith("step_") and not d.endswith(".tmp"))


def latest_step(ckpt_dir: str) -> Optional[int]:
    steps = checkpoint_steps(ckpt_dir)
    return max(steps) if steps else None


def verify(ckpt_dir: str, step: int) -> bool:
    """True iff the checkpoint's files are present and match their
    recorded sha256 checksums (torn writes and bit rot are *detected*,
    never silently restored).  Pre-checksum checkpoints
    (``format_version`` < 2) verify on file presence only.
    """
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    try:
        with open(os.path.join(path, "meta.json")) as f:
            meta = json.load(f)
    except (OSError, ValueError):
        return False
    checksums = meta.get("checksums")
    if checksums is None:                  # legacy format: presence only
        return os.path.exists(os.path.join(path, "arrays.npz"))
    try:
        return all(_sha256(os.path.join(path, name)) == want
                   for name, want in checksums.items())
    except OSError:
        return False


def valid_steps(ckpt_dir: str) -> List[int]:
    """Ascending step numbers whose checkpoints pass :func:`verify`."""
    return [s for s in checkpoint_steps(ckpt_dir) if verify(ckpt_dir, s)]


def latest_valid_step(ckpt_dir: str) -> Optional[int]:
    steps = valid_steps(ckpt_dir)
    return max(steps) if steps else None


def delete_step(ckpt_dir: str, step: int) -> None:
    """Remove one checkpoint directory (retention policy helper)."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    if os.path.isdir(path):
        shutil.rmtree(path)


def restore(ckpt_dir: str, step: int, like, shardings=None,
            zero_if_absent=()):
    """Restore into the structure of ``like``; reshard under ``shardings``.

    ``shardings`` may target a different mesh than the one that saved —
    leaves are device_put with the new sharding (elastic restart).  A leaf
    whose key is in ``zero_if_absent`` and that the checkpoint lacks (it
    was written before the leaf existed) restores as zeros of ``like``'s
    shape and dtype; any other missing leaf raises ``KeyError``.
    """
    path = os.path.join(ckpt_dir, f"step_{step:08d}")
    data = np.load(os.path.join(path, "arrays.npz"))
    flat, treedef = jax.tree_util.tree_flatten_with_path(like)
    shard_flat = (jax.tree_util.tree_leaves(shardings)
                  if shardings is not None else [None] * len(flat))
    leaves = []
    for (p, leaf), sh in zip(flat, shard_flat):
        key = _path_key(p)
        if key in zero_if_absent and key not in data.files:
            arr = np.zeros(leaf.shape, leaf.dtype)
        else:
            arr = data[key]
        assert arr.shape == tuple(leaf.shape), f"shape mismatch at {key}"
        if sh is not None:
            leaves.append(jax.device_put(arr, sh))
        else:
            leaves.append(jax.numpy.asarray(arr, dtype=leaf.dtype))
    return jax.tree_util.tree_unflatten(treedef, leaves)


def load_meta(ckpt_dir: str, step: int) -> dict:
    path = os.path.join(ckpt_dir, f"step_{step:08d}", "meta.json")
    with open(path) as f:
        return json.load(f)


def load_blob(ckpt_dir: str, step: int, name: str) -> bytes:
    """Read back one named blob written by :func:`save`."""
    path = os.path.join(ckpt_dir, f"step_{step:08d}", name)
    with open(path, "rb") as f:
        return f.read()
