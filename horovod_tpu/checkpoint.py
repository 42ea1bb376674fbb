"""Checkpoint / resume utilities.

The reference has no core checkpoint subsystem; it establishes three
conventions the examples implement (SURVEY §5.4):

1. **rank-0-only writing** (``README.md`` step 6,
   ``examples/tensorflow_mnist_estimator.py:147``),
2. **resume = rank-0 restore + broadcast to all ranks** including the resume
   epoch (``examples/keras_imagenet_resnet50.py:64-103``), and
3. **optimizer-state rewrapping on load** (``hvd.load_model``,
   ``horovod/keras/__init__.py:115-148``; ``broadcast_optimizer_state`` for
   torch).

This module packages those conventions TPU-natively on orbax (the JAX
checkpointing library): save is a no-op off rank 0; restore happens on rank
0 and is broadcast through the framework's collective path so every rank
resumes bit-identical state.
"""

from __future__ import annotations

import dataclasses
import importlib
import json
import os
import re
import shutil
import sys
from typing import Any, Dict, List, Optional, Tuple

from horovod_tpu import basics


def _checkpointer():
    import orbax.checkpoint as ocp
    return ocp.PyTreeCheckpointer()


def checkpoint_path(directory: str, epoch: int) -> str:
    # orbax requires absolute paths; accept relative ones at this API.
    return os.path.join(os.path.abspath(directory), f"checkpoint-{epoch}")


# ------------------------------------------------------------ delta chains
# The async snapshot stream (ckpt_stream.py) commits epochs as CHAIN
# directories instead of orbax trees: a committed ``checkpoint-N`` holding
# ``chain.json`` (manifest) and ``shards.npz`` (only the leaves whose bytes
# changed since the previous committed epoch).  A chain epoch is readable
# iff the manifest links ``prev`` hops back to a ``base`` epoch that still
# exists — :func:`chain_links` walks that list, and :func:`latest_epoch`
# only reports epochs whose full chain is intact, so a resume racing a
# crashed or garbage-collected writer falls back to the previous committed
# chain instead of picking a torn tip.

CHAIN_MANIFEST = "chain.json"
CHAIN_SHARDS = "shards.npz"

# Staging paths owned by a LIVE async writer, keyed by epoch: a concurrent
# synchronous save()'s _clean_stale must not reap an in-flight commit (the
# pre-chain cleaner could assume "no save running" because the single
# writer was the caller itself).
_ACTIVE_STAGING: Dict[int, str] = {}


class TornChainError(RuntimeError):
    """A chain checkpoint exists but one of its links (its base or an
    intermediate delta) is missing or unreadable, so the epoch cannot be
    reconstructed.  Resume paths catch this and fall back to the previous
    committed chain."""


def flatten_state(state: Any) -> Dict[str, Any]:
    """Flatten a pytree into ``{keystr(path): np.ndarray}`` — the on-host
    snapshot form the delta writer diffs and stores.  ``np.asarray`` on a
    ``jax.Array`` is the device→host copy; everything downstream of it is
    host-side work.  Key strings come from ``jax.tree_util.keystr`` and are
    stable for the dict/list/tuple trees training states are made of."""
    import numpy as np
    from jax.tree_util import keystr, tree_flatten_with_path
    flat = {}
    for path, leaf in tree_flatten_with_path(state)[0]:
        flat[keystr(path)] = np.asarray(leaf)
    return flat


def unflatten_like(like: Any, flat: Dict[str, Any]) -> Any:
    """Rebuild a pytree with ``like``'s structure from a flat snapshot.
    The key sets must match exactly — a template drift (renamed or added
    leaves) is a structural error, not something to paper over."""
    from jax.tree_util import keystr, tree_flatten_with_path, tree_unflatten
    paths_leaves, treedef = tree_flatten_with_path(like)
    keys = [keystr(p) for p, _ in paths_leaves]
    missing = [k for k in keys if k not in flat]
    extra = sorted(set(flat) - set(keys))
    if missing or extra:
        raise ValueError(
            f"chain checkpoint does not match the restore template: "
            f"missing leaves {missing[:4]!r}, unexpected leaves "
            f"{extra[:4]!r}")
    return tree_unflatten(treedef, [flat[k] for k in keys])


def _chain_manifest(directory: str, epoch: int) -> Optional[dict]:
    p = os.path.join(checkpoint_path(directory, epoch), CHAIN_MANIFEST)
    try:
        with open(p) as f:
            return json.load(f)
    except (OSError, ValueError):
        return None


def is_chain(directory: str, epoch: int) -> bool:
    """True when ``checkpoint-{epoch}`` is a committed chain directory
    (vs a legacy orbax tree or nothing at all)."""
    return _chain_manifest(directory, epoch) is not None


def chain_links(directory: str, epoch: int) -> Optional[List[int]]:
    """Epochs to replay, base first, to reconstruct chain ``epoch`` —
    or None when the chain is torn (a link missing, unreadable, cyclic,
    or not anchored to a base)."""
    links: List[int] = []
    e = epoch
    while True:
        m = _chain_manifest(directory, e)
        if m is None:
            return None
        links.append(e)
        if m.get("kind") == "base":
            return list(reversed(links))
        prev = m.get("prev", -1)
        # prev must strictly decrease — anything else is corrupt/cyclic.
        if not isinstance(prev, int) or not 0 <= prev < e:
            return None
        e = prev


def _link_crc_ok(directory: str, epoch: int) -> bool:
    """Verify one chain link's shard file against the CRC32C its manifest
    recorded at commit.  Links from before the integrity trailer (no
    ``crc32c`` key) pass — there is nothing to check them against."""
    m = _chain_manifest(directory, epoch)
    want = None if m is None else m.get("crc32c")
    if want is None:
        return True
    from horovod_tpu import metrics, wire
    try:
        with open(os.path.join(checkpoint_path(directory, epoch),
                               CHAIN_SHARDS), "rb") as f:
            got = wire.crc32c(f.read())
    except OSError:
        return False
    if got != (want & 0xFFFFFFFF):
        metrics.registry.inc("ckpt.corrupt_links")
        return False
    return True


def _is_committed(directory: str, epoch: int) -> bool:
    """True when ``checkpoint-{epoch}`` is restorable: a legacy orbax dir
    (atomic-replace committed, hence complete) or a chain dir whose links
    are all intact AND whose shard bytes still match the CRC32C recorded
    at commit (a corrupt link makes the whole chain torn — the resume
    pivots to the prior committed chain, never loads flipped bits)."""
    if not os.path.isdir(checkpoint_path(directory, epoch)):
        return False
    if is_chain(directory, epoch):
        links = chain_links(directory, epoch)
        if links is None:
            return False
        return all(_link_crc_ok(directory, e) for e in links)
    return True


def save_chain(directory: str, flat: Dict[str, Any], epoch: int, *,
               prev_epoch: int = -1,
               prev_flat: Optional[Dict[str, Any]] = None,
               fault_hook=None) -> Dict[str, Any]:
    """Commit one chain epoch atomically: a full ``base`` when
    ``prev_flat`` is None (or the leaf set changed), else a ``delta``
    holding only the leaves whose bytes differ from ``prev_flat`` (the
    last COMMITTED snapshot, anchored at ``prev_epoch``).

    Same commit discipline as :func:`save`: world sidecar first, shards
    staged under a dot-prefixed dir ``latest_epoch`` can never match, one
    ``os.replace`` to publish.  ``fault_hook`` (chaos drills) runs after
    the shards are staged but before the commit — the worst place to die.

    Returns ``{"kind", "epoch", "nbytes", "shards", "total"}``.  The
    single-writer convention is the caller's job (ckpt_stream runs this
    on the owning rank's writer thread only).
    """
    import numpy as np
    directory = os.path.abspath(directory)
    os.makedirs(directory, exist_ok=True)
    path = checkpoint_path(directory, epoch)
    if prev_flat is not None and set(prev_flat) != set(flat):
        prev_flat = None   # leaf set changed: a delta cannot express it
    if prev_flat is None:
        changed = sorted(flat)
        kind = "base"
    else:
        changed = sorted(
            k for k, v in flat.items()
            if v.shape != prev_flat[k].shape
            or v.dtype != prev_flat[k].dtype
            or v.tobytes() != prev_flat[k].tobytes())
        kind = "delta"
    staging = os.path.join(directory,
                           f".tmp-checkpoint-{epoch}-{os.getpid()}")
    _ACTIVE_STAGING[epoch] = staging
    try:
        # Sidecar before the commit, same ordering argument as save().
        try:
            world = {"world_size": basics.size(),
                     "process_count": basics.process_count()}
        except Exception:
            world = None   # usable before init (tests, offline tools)
        if world is not None:
            _write_atomic(_world_meta_path(directory, epoch),
                          json.dumps(world))
        shutil.rmtree(staging, ignore_errors=True)
        os.makedirs(staging)
        np.savez(os.path.join(staging, CHAIN_SHARDS),
                 **{k: np.asarray(flat[k]) for k in changed})
        from horovod_tpu import wire
        with open(os.path.join(staging, CHAIN_SHARDS), "rb") as f:
            shard_crc = wire.crc32c(f.read())
        if fault_hook is not None:
            fault_hook()
        manifest = {"format": 1, "kind": kind, "epoch": epoch,
                    "prev": prev_epoch if kind == "delta" else -1,
                    "keys": sorted(flat), "shards": changed,
                    "crc32c": shard_crc}
        _write_atomic(os.path.join(staging, CHAIN_MANIFEST),
                      json.dumps(manifest))
        if os.path.isdir(path):
            shutil.rmtree(path)   # re-commit of the same epoch
        os.replace(staging, path)
    finally:
        _ACTIVE_STAGING.pop(epoch, None)
    nbytes = int(sum(np.asarray(flat[k]).nbytes for k in changed))
    return {"kind": kind, "epoch": epoch, "nbytes": nbytes,
            "shards": len(changed), "total": len(flat)}


def read_chain_state(directory: str, epoch: int) -> Dict[str, Any]:
    """Replay the base+delta chain ending at ``epoch`` into a flat
    snapshot.  Raises :class:`TornChainError` when the chain is torn."""
    import numpy as np
    links = chain_links(directory, epoch)
    if links is None:
        raise TornChainError(
            f"checkpoint-{epoch} in {directory!r} is a torn chain (a "
            f"base or delta link is missing); latest committed epoch "
            f"is {latest_epoch(directory)}")
    flat: Dict[str, Any] = {}
    for e in links:
        shard_path = os.path.join(checkpoint_path(directory, e),
                                  CHAIN_SHARDS)
        # End-to-end integrity: the manifest carries a CRC32C of the
        # shard file taken at commit; a mismatch (bit rot, a torn write
        # the rename discipline couldn't see, a chaos drill) makes the
        # whole chain torn — the caller falls back to the prior
        # committed chain instead of loading silently wrong numbers.
        if not _link_crc_ok(directory, e):
            raise TornChainError(
                f"checkpoint-{e} (link of chain {epoch}) in "
                f"{directory!r} is corrupt: shard CRC32C does not match "
                f"the manifest recorded at commit")
        try:
            with np.load(shard_path, allow_pickle=False) as z:
                for k in z.files:
                    flat[k] = z[k]
        except (OSError, ValueError) as exc:
            raise TornChainError(
                f"checkpoint-{e} (link of chain {epoch}) in "
                f"{directory!r} is unreadable: {exc}") from exc
    keys = _chain_manifest(directory, epoch)["keys"]
    missing = [k for k in keys if k not in flat]
    if missing:
        raise TornChainError(
            f"chain {epoch} in {directory!r} replayed without leaves "
            f"{missing[:4]!r} — base was overwritten by a narrower state")
    return {k: flat[k] for k in keys}


def resolve_committed_epoch(directory: str, epoch: int) -> int:
    """``epoch`` if it is committed (legacy or intact chain), else the
    highest committed epoch below it, else -1.  The torn-tip fallback:
    rank 0 runs this before the restore broadcast so no rank ever starts
    restoring an epoch that cannot be read."""
    if epoch >= 0 and _is_committed(directory, epoch):
        return epoch
    best = -1
    if os.path.isdir(directory):
        for entry in os.listdir(directory):
            m = re.fullmatch(r"checkpoint-(\d+)", entry)
            if m and best < int(m.group(1)) < epoch and _is_committed(
                    directory, int(m.group(1))):
                best = int(m.group(1))
    return best


def save(directory: str, state: Any, epoch: int) -> Optional[str]:
    """Write a checkpoint on rank 0 only; other ranks no-op (convention 1).

    ``state`` is any pytree (e.g. ``{"params": ..., "opt_state": ...}``).

    The commit is atomic: orbax writes into a dot-prefixed staging
    directory that :func:`latest_epoch` can never match, and a single
    ``os.replace`` publishes it as ``checkpoint-{epoch}``.  A crash
    mid-save therefore leaves debris (cleaned up by the next save), never
    a half-written directory a resume would restore from.
    """
    if basics.rank() != 0:
        return None
    directory = os.path.abspath(directory)
    path = checkpoint_path(directory, epoch)
    os.makedirs(directory, exist_ok=True)
    _clean_stale(directory, saving=epoch)
    # World-size sidecar lands BEFORE the checkpoint commits (same
    # ordering argument as the optimizer spec): an elastic resume that
    # sees checkpoint-N can always tell what world wrote it.  An orphan
    # sidecar from a crash before the commit below is harmless —
    # latest_epoch only matches committed checkpoint dirs — and is
    # removed by the next save's _clean_stale.
    _write_atomic(_world_meta_path(directory, epoch),
                  json.dumps({"world_size": basics.size(),
                              "process_count": basics.process_count()}))
    staging = os.path.join(directory, f".tmp-checkpoint-{epoch}-{os.getpid()}")
    _checkpointer().save(staging, state, force=True)
    if os.path.isdir(path):
        shutil.rmtree(path)   # force=True re-save of the same epoch
    os.replace(staging, path)
    return path


def _write_atomic(path: str, text: str) -> None:
    """Publish ``text`` at ``path`` via a same-directory temp file and
    ``os.replace``, so no reader ever sees a partially-written file."""
    tmp = path + ".tmp"
    with open(tmp, "w") as f:
        f.write(text)
    os.replace(tmp, path)


def _clean_stale(directory: str, saving: Optional[int] = None) -> None:
    """Remove debris a mid-save crash can leave behind: uncommitted
    staging directories, half-written sidecar temp files, and orphan
    sidecars whose checkpoint never committed.  Runs in the single
    writer (rank 0) at save time.  Staging dirs registered by a live
    async writer (``_ACTIVE_STAGING``) are in flight, not stale — the
    background delta writer may be mid-commit while a synchronous
    ``save()`` runs on the training thread.  Nor are the sidecars of the
    epoch ``saving`` now: :func:`save_model` has just written its
    optimizer spec, ahead of the commit."""
    entries = set(os.listdir(directory))
    active = {os.path.basename(p) for p in _ACTIVE_STAGING.values()}
    active_epochs = {f"checkpoint-{e}" for e in (*_ACTIVE_STAGING, saving)}
    for entry in entries:
        p = os.path.join(directory, entry)
        if re.fullmatch(r"\.tmp-checkpoint-\d+-\d+", entry):
            if entry not in active:
                shutil.rmtree(p, ignore_errors=True)
        elif re.fullmatch(
                r"checkpoint-\d+\.(world|optimizer)\.json\.tmp", entry):
            try:
                os.remove(p)
            except OSError:
                pass
        else:
            m = re.fullmatch(r"(checkpoint-\d+)\.(world|optimizer)\.json",
                             entry)
            if (m and m.group(1) not in entries
                    and m.group(1) not in active_epochs):
                try:
                    os.remove(p)
                except OSError:
                    pass


def _world_meta_path(directory: str, epoch: int) -> str:
    return checkpoint_path(directory, epoch) + ".world.json"


def saved_world_size(directory: str, epoch: int) -> int:
    """World size recorded when checkpoint ``epoch`` was written, or -1
    for checkpoints predating the sidecar (or an unreadable one)."""
    p = _world_meta_path(directory, epoch)
    try:
        with open(p) as f:
            return int(json.load(f).get("world_size", -1))
    except (OSError, ValueError):
        return -1


def _sharded_leaf_path(tree) -> Optional[str]:
    """Path of the first leaf laid out across devices (not fully
    replicated), or None.  Such state is bound to a specific world shape
    and cannot survive an elastic world-size change."""
    import jax
    from jax.tree_util import keystr, tree_flatten_with_path
    for path, leaf in tree_flatten_with_path(tree)[0]:
        if isinstance(leaf, jax.Array) and not leaf.is_fully_replicated:
            return keystr(path)
    return None


def latest_epoch(directory: str) -> int:
    """Highest epoch with a COMMITTED checkpoint in ``directory``, or -1.

    Mirrors the reference's resume-epoch scan
    (``examples/keras_imagenet_resnet50.py:64-70``: try epochs descending,
    first existing file wins).  Only committed checkpoint directories
    count: :func:`save` stages under a dot-prefixed name the pattern
    can never match and publishes atomically, so an entry seen here is
    complete — sidecars, stray files, and dot-prefixed staging debris
    from a crashed save are skipped.  A chain epoch additionally counts
    only when every link back to its base is intact, so a resume racing
    a crashed delta writer falls back past the torn tip.
    """
    if not os.path.isdir(directory):
        return -1
    best = -1
    for entry in os.listdir(directory):
        m = re.fullmatch(r"checkpoint-(\d+)", entry)
        if m and int(m.group(1)) > best and _is_committed(
                directory, int(m.group(1))):
            best = int(m.group(1))
    return best


def restore(directory: str, epoch: int, like: Any) -> Any:
    """Restore the checkpoint for ``epoch`` with the structure of ``like``.

    A chain epoch (async incremental stream) replays its base+delta links;
    raises :class:`TornChainError` if a link is missing.  A legacy orbax
    epoch restores with ``item=like`` so orbax rebuilds the original
    pytree structure (optax states are NamedTuples/tuples, which the
    stored metadata alone round-trips as lists).
    """
    import time
    t0 = time.perf_counter()
    if is_chain(directory, epoch):
        out = unflatten_like(like, read_chain_state(directory, epoch))
    else:
        import orbax.checkpoint as ocp
        path = checkpoint_path(directory, epoch)
        out = _checkpointer().restore(
            path, item=like,
            restore_args=ocp.checkpoint_utils.construct_restore_args(like))
    from horovod_tpu import metrics
    metrics.registry.observe("ckpt.restore_seconds",
                             time.perf_counter() - t0)
    return out


@dataclasses.dataclass
class OptimizerSpec:
    """Serializable optimizer identity — the optax analogue of the Keras
    optimizer config the reference persists inside its h5 files
    (``horovod/keras/__init__.py:113-148``: class name + hyperparams,
    reconstructed at load with ``custom_optimizers`` resolution).

    optax transforms are closures, so identity is declared rather than
    introspected: an ordered list of ``(factory, kwargs)`` steps, each
    factory a dotted import path (``"optax.adamw"``) or a name resolved
    from ``custom_objects`` at build time (the reference's
    ``custom_optimizers``/``custom_objects`` escape hatch).  Multiple
    steps rebuild as ``optax.chain(*steps)``.
    """

    steps: List[Tuple[str, Dict[str, Any]]]

    @classmethod
    def of(cls, factory: str, **kwargs) -> "OptimizerSpec":
        return cls([(factory, kwargs)])

    @classmethod
    def chain(cls, *steps) -> "OptimizerSpec":
        return cls([(f, dict(kw)) for f, kw in steps])

    def to_json(self) -> str:
        return json.dumps({"steps": [[f, kw] for f, kw in self.steps]})

    @classmethod
    def from_json(cls, text: str) -> "OptimizerSpec":
        data = json.loads(text)
        return cls([(f, kw) for f, kw in data["steps"]])

    def build(self, custom_objects: Optional[Dict[str, Any]] = None):
        import optax
        txs = []
        for factory, kwargs in self.steps:
            fn = None
            if custom_objects and factory in custom_objects:
                fn = custom_objects[factory]
            else:
                mod_name, _, attr = factory.rpartition(".")
                # The spec file sits on disk next to the checkpoint;
                # resolving arbitrary dotted paths from it would hand a
                # tampered directory code execution at resume.  Only the
                # optax namespace auto-imports — everything else must
                # come through the caller's custom_objects.
                if mod_name != "optax" and not mod_name.startswith(
                        "optax."):
                    raise ValueError(
                        f"optimizer factory {factory!r} is neither an "
                        f"optax.* path nor in custom_objects "
                        f"{sorted(custom_objects or {})}; pass it via "
                        "load_model(custom_objects={...})")
                fn = getattr(importlib.import_module(mod_name), attr)
            txs.append(fn(**kwargs))
        return txs[0] if len(txs) == 1 else optax.chain(*txs)


def _as_optimizer_spec(optimizer) -> OptimizerSpec:
    if isinstance(optimizer, OptimizerSpec):
        return optimizer
    if (isinstance(optimizer, tuple) and len(optimizer) == 2
            and isinstance(optimizer[0], str)):
        return OptimizerSpec([(optimizer[0], dict(optimizer[1]))])
    if isinstance(optimizer, list):
        return OptimizerSpec.chain(*optimizer)
    raise TypeError(
        "save_model(optimizer=...) takes an OptimizerSpec, a "
        "(factory, kwargs) tuple, or a list of them — a raw optax "
        "GradientTransformation is a closure and cannot be persisted; "
        "declare how to rebuild it instead (see checkpoint.OptimizerSpec)")


def _optimizer_spec_path(directory: str, epoch: int) -> str:
    return checkpoint_path(directory, epoch) + ".optimizer.json"


# ------------------------------------------------------ params skeleton
# load_model-with-only-a-directory needs every rank to hold a pytree of
# the right structure before the value broadcast; rank 0 derives this
# structural spec from the checkpoint's METADATA (shapes/dtypes only — no
# data read) and broadcasts it as bytes.  Orbax stores tuples as lists
# and JSON keys are strings, so a params tree containing tuple nodes or
# non-string dict keys cannot round-trip without an explicit
# ``params_like`` — :func:`save_model` warns at save time.

def _meta_to_spec(node) -> Any:
    if node is None:
        return {"t": "none"}
    if isinstance(node, dict):
        return {"t": "dict",
                "items": {k: _meta_to_spec(v) for k, v in node.items()}}
    if isinstance(node, (list, tuple)):
        return {"t": "list", "items": [_meta_to_spec(v) for v in node]}
    return {"t": "leaf", "dtype": str(node.dtype),
            "shape": list(node.shape)}


def _params_resume_safe(tree) -> bool:
    """True when the params tree survives the metadata→JSON→skeleton trip
    structurally intact: PLAIN dicts with string keys / plain lists, down
    to array-or-scalar leaves.  Anything else — tuples, FrozenDict-style
    mappings, custom pytree nodes — rebuilds as a different node type (or
    not at all) from the JSON skeleton, so it is reported unsafe and
    :func:`save_model` warns."""
    import numpy as np
    if type(tree) is dict:
        return (all(isinstance(k, str) for k in tree)
                and all(_params_resume_safe(v) for v in tree.values()))
    if type(tree) is list:
        return all(_params_resume_safe(v) for v in tree)
    if isinstance(tree, (np.ndarray, np.generic, int, float, complex)):
        return True
    import jax
    return isinstance(tree, jax.Array)


def _spec_to_skeleton(spec) -> Any:
    import jax.numpy as jnp
    t = spec["t"]
    if t == "none":
        return None
    if t == "dict":
        return {k: _spec_to_skeleton(v) for k, v in spec["items"].items()}
    if t == "list":
        return [_spec_to_skeleton(v) for v in spec["items"]]
    return jnp.zeros(tuple(spec["shape"]), jnp.dtype(spec["dtype"]))


def _broadcast_text(text: Optional[str], root_rank: int, name: str) -> str:
    """Broadcast a variable-length UTF-8 string from ``root_rank``:
    length first (fixed-shape negotiated broadcast), then the payload."""
    import numpy as np
    from horovod_tpu.ops import eager
    data = (text or "").encode("utf-8")
    n = int(np.asarray(eager.broadcast(
        np.asarray(len(data), np.int64), root_rank, name=f"{name}.len")))
    buf = np.zeros(n, np.uint8)
    if basics.rank() == root_rank:
        buf = np.frombuffer(data, np.uint8).copy()
    out = np.asarray(eager.broadcast(buf, root_rank, name=f"{name}.bytes"))
    return out.tobytes().decode("utf-8")


def save_model(directory: str, params: Any, opt_state: Any,
               epoch: int, optimizer=None) -> Optional[str]:
    """Save a full training state (params + optimizer state) under the
    ``{"params", "opt_state"}`` convention :func:`load_model` restores.
    Rank-0-only like :func:`save`.

    ``optimizer`` (an :class:`OptimizerSpec`, ``(factory, kwargs)`` tuple,
    or list of them) additionally persists the optimizer *identity* next
    to the checkpoint, enabling :func:`load_model` to resume from the
    directory alone — the reference's serialize-the-optimizer-too
    behaviour (``horovod/keras/__init__.py:113-148``)."""
    spec = _as_optimizer_spec(optimizer) if optimizer is not None else None
    if spec is not None and not _params_resume_safe(params):
        import warnings
        warnings.warn(
            "save_model: this params tree contains tuple nodes or "
            "non-string dict keys, which the directory-only load_model "
            "skeleton cannot reproduce (orbax stores tuples as lists; "
            "JSON keys are strings) — resuming will need an explicit "
            "params_like=.", stacklevel=2)
    # The spec lands BEFORE the checkpoint commits: a concurrent
    # directory-only load_model that sees checkpoint-N must always find
    # N's spec (a stale spec without its checkpoint is harmless —
    # latest_epoch only matches checkpoint dirs).
    if basics.rank() == 0 and spec is not None:
        os.makedirs(os.path.abspath(directory), exist_ok=True)
        _write_atomic(_optimizer_spec_path(directory, epoch),
                      spec.to_json())
    return save(directory, {"params": params, "opt_state": opt_state},
                epoch)


def load_model(directory: str, optimizer=None, params_like: Any = None, *,
               root_rank: int = 0, average: bool = True,
               compression=None, custom_objects=None):
    """One-call resume with the optimizer re-wrapped distributed — the
    reference's ``hvd.load_model`` (``horovod/keras/__init__.py:115-148``,
    ``_impl.py:93-109``: restore the saved model, reconstruct its
    optimizer from the file, wrap in DistributedOptimizer, broadcast).

    Args:
      directory: checkpoint directory written by :func:`save_model`.
      optimizer: the PLAIN optax optimizer (any chain, custom or not) —
        wrapped in :func:`horovod_tpu.jax.DistributedOptimizer` here,
        exactly like the reference rewraps the deserialized optimizer
        class.  **Omit it** to rebuild the optimizer from the
        :class:`OptimizerSpec` persisted by
        ``save_model(..., optimizer=...)``; ``custom_objects`` resolves
        non-importable factory names then (the reference's
        ``custom_optimizers``/``custom_objects``).
      params_like: a params pytree of the right structure/shapes (e.g.
        from ``model.init``) used both as the restore skeleton and as
        the fresh state when no checkpoint exists.  **Omit it** to derive
        the skeleton from the checkpoint's metadata (no data read; the
        structure is broadcast from rank 0).  Params built of
        string-keyed dicts / lists of arrays round-trip; tuple nodes,
        non-string keys, and custom pytree nodes need an explicit
        ``params_like`` (``save_model`` warns about such trees).
      average / compression: forwarded to ``DistributedOptimizer``.

    Returns ``(params, distributed_tx, opt_state, resume_epoch)``;
    ``resume_epoch`` is -1 (fresh params/opt_state, still broadcast from
    ``root_rank``) when the directory holds no checkpoint — starting
    fresh requires ``optimizer`` and ``params_like``.  The returned
    ``opt_state`` preserves the optimizer's own pytree structure through
    the round trip, custom chains included (the reference round-trips
    custom optimizers in ``test/test_keras.py:60-183``).
    """
    import numpy as np
    from horovod_tpu.compression import NoneCompressor
    from horovod_tpu.jax import DistributedOptimizer
    from horovod_tpu.ops import eager

    if compression is None:
        compression = NoneCompressor
    if isinstance(optimizer, OptimizerSpec):
        # Accept the same spec save_model's optimizer= takes — build it
        # rather than surfacing an AttributeError from optimizer.init.
        optimizer = optimizer.build(custom_objects)
    agreed_epoch = None
    if optimizer is None or params_like is None:
        # Directory-only resume: agree on the epoch ONCE, then both the
        # reconstruction here and the restore below use it — a checkpoint
        # landing concurrently must not split the spec/skeleton and the
        # weights across two different epochs.
        epoch = latest_epoch(directory) if basics.rank() == root_rank else -1
        epoch = int(np.asarray(eager.broadcast(
            np.asarray(epoch, np.int64), root_rank,
            name="ckpt.spec_epoch")))
        agreed_epoch = epoch
        if epoch < 0:
            raise FileNotFoundError(
                f"load_model: no checkpoint in {directory!r} to "
                "reconstruct from; pass optimizer= and params_like= to "
                "start fresh")
        if optimizer is None:
            spec_text = None
            if basics.rank() == root_rank:
                p = _optimizer_spec_path(directory, epoch)
                spec_text = open(p).read() if os.path.exists(p) else ""
            spec_text = _broadcast_text(spec_text, root_rank,
                                        "ckpt.optspec")
            if not spec_text:
                raise FileNotFoundError(
                    f"load_model: checkpoint-{epoch} in {directory!r} was "
                    "saved without an optimizer spec (save_model's "
                    "optimizer= argument); pass optimizer= explicitly")
            optimizer = OptimizerSpec.from_json(spec_text).build(
                custom_objects)
        if params_like is None:
            skel_json = None
            if basics.rank() == root_rank:
                # Metadata only — shapes/dtypes without reading the
                # checkpoint data (the values are read once, below, in
                # restore_and_broadcast).
                meta = _checkpointer().metadata(
                    checkpoint_path(directory, epoch))
                tree = meta.item_metadata.tree
                skel_json = json.dumps(_meta_to_spec(tree["params"]))
            skel_json = _broadcast_text(skel_json, root_rank, "ckpt.pskel")
            params_like = _spec_to_skeleton(json.loads(skel_json))
    tx = DistributedOptimizer(optimizer, average=average,
                              compression=compression)
    like = {"params": params_like, "opt_state": optimizer.init(params_like)}
    state, epoch = restore_and_broadcast(directory, like,
                                         root_rank=root_rank,
                                         epoch=agreed_epoch)
    return state["params"], tx, state["opt_state"], epoch


def restore_and_broadcast(directory: str, like: Any,
                          root_rank: int = 0,
                          epoch: Optional[int] = None,
                          optional_keys: Tuple[str, ...] = ()
                          ) -> Tuple[Any, int]:
    """Resume protocol (conventions 2+3): the resume epoch is agreed by
    broadcasting rank 0's scan; rank 0 restores; state is broadcast so all
    ranks start identical (reference ``keras_imagenet_resnet50.py:64-103``,
    ``pytorch_imagenet_resnet50.py:71,134-142``).

    Returns ``(state, resume_epoch)``; ``resume_epoch`` is -1 (and ``state``
    is ``like``, broadcast from root) when no checkpoint exists.  Pass an
    explicit ``epoch`` (already agreed across ranks) to restore that
    checkpoint instead of re-scanning — callers that derived other state
    from an epoch must restore the SAME one even if a new checkpoint
    lands concurrently.

    ``optional_keys`` (``like`` must be a dict): top-level template keys
    tolerated as absent on disk — rank 0 checks the checkpoint's
    metadata and the presence set is agreed across ranks BEFORE the
    value broadcast, so a checkpoint written by an older script version
    (e.g. without ``opt_state``) resumes cleanly — the corresponding
    ``like`` values pass through untouched — instead of rank 0 raising
    a tree-structure error while the other ranks hang in the broadcast.
    """
    import numpy as np
    from horovod_tpu.jax import broadcast_parameters
    from horovod_tpu.ops import eager

    if epoch is None:
        epoch = latest_epoch(directory) if basics.rank() == root_rank else -1
        epoch = int(np.asarray(eager.broadcast(
            np.asarray(epoch, np.int64), root_rank,
            name="ckpt.resume_epoch")))
    if epoch >= 0:
        # Torn-tip fallback, agreed BEFORE any value broadcast: rank 0
        # validates the chosen epoch is committed (an explicitly passed
        # epoch may be a chain whose base was lost, or debris from a
        # writer that died mid-commit) and every rank pivots to the same
        # fallback — rank 0 must never discover a torn chain after the
        # other ranks have entered the restore broadcast.
        tip = (resolve_committed_epoch(directory, epoch)
               if basics.rank() == root_rank else -1)
        tip = int(np.asarray(eager.broadcast(
            np.asarray(tip, np.int64), root_rank,
            name="ckpt.chain_tip")))
        if tip != epoch:
            print(
                f"horovod_tpu checkpoint: checkpoint-{epoch} in "
                f"{directory!r} is torn or missing; falling back to "
                + (f"committed checkpoint-{tip}" if tip >= 0
                   else "fresh state (no committed checkpoint)"),
                file=sys.stderr)
        epoch = tip
    if epoch >= 0:
        # Elastic resume: the world that wrote the checkpoint may be gone
        # (a rank was lost and the job reconfigured).  Replicated state
        # re-broadcasts from root at ANY world size; state laid out across
        # devices is bound to the old world shape and must fail with a
        # named leaf, not a shape error deep inside orbax.
        saved = (saved_world_size(directory, epoch)
                 if basics.rank() == root_rank else -1)
        saved = int(np.asarray(eager.broadcast(
            np.asarray(saved, np.int64), root_rank,
            name="ckpt.world_size")))
        cur = basics.size()
        if saved >= 0 and saved != cur:
            bad = _sharded_leaf_path(like)
            if bad is not None:
                raise ValueError(
                    f"restore_and_broadcast: checkpoint-{epoch} in "
                    f"{directory!r} was saved at world size {saved} but "
                    f"the job is now size {cur}, and template leaf "
                    f"{bad!r} is sharded across devices — sharded state "
                    "cannot be re-laid-out across a different world; "
                    "only replicated state survives an elastic "
                    "world-size change (see docs/elasticity.md)")
            print(
                f"horovod_tpu checkpoint: checkpoint-{epoch} was written "
                f"at world size {saved}; restoring into world size {cur} "
                f"— replicated state re-broadcast from rank {root_rank}",
                file=sys.stderr)
    if optional_keys and not isinstance(like, dict):
        # Fail on the FIRST call, not on the first resume after a
        # checkpoint exists.
        raise TypeError(
            "optional_keys needs a dict template (top-level keys)")
    if optional_keys and epoch >= 0:
        present = 0
        if basics.rank() == root_rank:
            if is_chain(directory, epoch):
                leaf_keys = _chain_manifest(directory, epoch)["keys"]
                present = sum(
                    1 << i for i, k in enumerate(optional_keys)
                    if any(s.startswith(f"['{k}']") for s in leaf_keys))
            else:
                tree = _checkpointer().metadata(
                    checkpoint_path(directory, epoch)).item_metadata.tree
                present = sum(1 << i for i, k in enumerate(optional_keys)
                              if k in tree)
        present = int(np.asarray(eager.broadcast(
            np.asarray(present, np.int64), root_rank,
            name="ckpt.optional_keys")))
        missing = {k for i, k in enumerate(optional_keys)
                   if not (present >> i) & 1}
        # Restore without the absent keys; their template values are
        # merged back before the broadcast below, so every rank ends
        # with root's copy of the defaults too (a fresh opt_state built
        # pre-broadcast may differ per rank).
        defaults = {k: like[k] for k in optional_keys
                    if k in missing and k in like}
        like = {k: v for k, v in like.items() if k not in missing}
    else:
        defaults = {}
    state = like
    if epoch >= 0 and basics.rank() == root_rank:
        state = restore(directory, epoch, like)
    if defaults:
        state = {**state, **defaults}
    state = broadcast_parameters(state, root_rank,
                                 name_prefix="ckpt.broadcast")
    return state, epoch
