"""Durable checkpointing + crash recovery for the materialization engine.

The train side already survives preemption (``repro.train.checkpoint`` /
``repro.train.fault``); this module gives the KB engine the same story at
materialization-round granularity.  Set ``REPRO_CKPT_DIR`` and every
executor — two-phase, fused, distributed — checkpoints its host-consistent
state at round/phase boundaries and resumes from the newest valid
checkpoint on the next run.

Checkpoint layout (one directory per tag, tag = completed-round cursor)::

    <REPRO_CKPT_DIR>/ckpt_00000042/
        shard_0.npz        per-shard payload: store__<pred> / delta__<pred>
        shard_1.npz        valid rows (trimmed, lexsorted per shard);
        ...                base__<pred> rides shard 0
        dict.pkl           Dictionary.state_dict() (term <-> id interning)
        caps.pkl           _Caps.state() (converged capacity plan)
        MANIFEST.json      tag + run meta + sha256 per payload file

Atomicity and integrity: payloads are written into a ``.tmp`` sibling,
the manifest (with content checksums) is written and fsynced LAST, and the
directory is atomically renamed into place — a crash mid-save leaves
either the previous checkpoint or a ``.tmp`` directory the loader ignores.
On load, every file is re-hashed against the manifest; a corrupt or
half-written checkpoint is skipped and the next-newest valid one is used.

Executor neutrality and elasticity: checkpointed state is *host* data —
trimmed rows, the dictionary, the round cursor — with no device placement
baked in.  A run checkpointed by the distributed executor at ndev=4
restores into the fused executor, the two-phase executor, or a dist run
at any other ndev: the loader concatenates the per-shard rows and the
restoring executor re-partitions by the same full-tuple hash its
exchanges use (``distributed.np_tuple_hash``), so every fact lands back
on its canonical home for the new mesh shape.

Resume correctness: semi-naive restart from a partially-materialized
store alone would terminate immediately (everything already derived in
earlier rounds is IN the store, so round one's "fresh" set is empty) —
checkpoints therefore persist the LIVE DELTAS next to the stores, and
``maybe_resume`` hands them back as the seed of the continued fixpoint.

``PreemptionGuard`` integration: when checkpointing is enabled the
engine installs a chained SIGTERM guard; the flag is polled at the same
boundaries (never mid-program), the executor saves a final consistent
checkpoint and exits with status 143.

Fault rehearsal: every boundary also consults ``repro.engine.faultinject``
(``REPRO_FAULT_SPEC``) — injected crashes land *after* any due save, so a
killed run always leaves its latest durable state behind (exactly the
guarantee a real SIGKILL gets).
"""
from __future__ import annotations

import hashlib
import json
import os
import pickle
import shutil

import numpy as np
from jax.profiler import TraceAnnotation

from repro.engine import faultinject
from repro.engine.relation import Relation, lex_order

FORMAT = 1


# ---------------------------------------------------------------------------
# env knobs
# ---------------------------------------------------------------------------
def ckpt_dir() -> str | None:
    """Checkpoint directory (``REPRO_CKPT_DIR``); None disables durability."""
    return os.environ.get("REPRO_CKPT_DIR") or None


def ckpt_every() -> int:
    """Save cadence in completed rounds (``REPRO_CKPT_EVERY``, default 1 —
    every boundary; boundaries are already rare for the compiled executors:
    phase exits, not rounds)."""
    return max(int(os.environ.get("REPRO_CKPT_EVERY", "1")), 1)


def ckpt_keep() -> int:
    """How many newest checkpoints survive GC (``REPRO_CKPT_KEEP``)."""
    return max(int(os.environ.get("REPRO_CKPT_KEEP", "3")), 1)


def kb_fingerprint(kb, mode: str) -> str:
    """Identity of a materialization run for resume matching: the rule set,
    the mode, and the store dtype.  Deliberately EXCLUDES the executor and
    the device count — checkpoints restore across both."""
    h = hashlib.sha256()
    for rule in kb.program.rules:
        h.update(repr(rule).encode())
        h.update(b"\n")
    h.update(mode.encode())
    h.update(str(np.dtype(kb.dict.id_dtype)).encode())
    return h.hexdigest()[:16]


def _sha256(path: str) -> str:
    h = hashlib.sha256()
    with open(path, "rb") as f:
        for block in iter(lambda: f.read(1 << 20), b""):
            h.update(block)
    return h.hexdigest()


# ---------------------------------------------------------------------------
# durable store
# ---------------------------------------------------------------------------
class RecoveryManager:
    """Atomic, checksummed checkpoint directory store.

    ``save`` is temp-then-rename with the manifest written last;
    ``load`` walks tags newest-first and returns the first checkpoint
    whose manifest parses, whose fingerprint matches, and whose payload
    checksums verify — anything else is skipped (and a crashed save's
    ``.tmp`` litter is ignored entirely)."""

    def __init__(self, directory: str, keep: int | None = None):
        self.dir = directory
        self.keep = ckpt_keep() if keep is None else keep
        os.makedirs(directory, exist_ok=True)

    def _path(self, tag: int) -> str:
        return os.path.join(self.dir, f"ckpt_{tag:08d}")

    def tags(self) -> list:
        out = []
        for d in os.listdir(self.dir):
            if d.startswith("ckpt_") and os.path.isfile(
                    os.path.join(self.dir, d, "MANIFEST.json")):
                try:
                    out.append(int(d.split("_")[1]))
                except ValueError:
                    continue
        return sorted(out)

    def drop(self, tag: int) -> None:
        shutil.rmtree(self._path(tag), ignore_errors=True)

    # ------------------------------------------------------------------
    def save(self, tag: int, meta: dict, shards, blobs: dict) -> str:
        """Write one checkpoint: ``shards`` is a list of per-shard
        ``{name: np.ndarray}`` payloads, ``blobs`` maps extra filenames to
        bytes.  Returns the committed directory path."""
        tmp = os.path.join(self.dir, f".tmp_ckpt_{tag:08d}")
        if os.path.exists(tmp):
            shutil.rmtree(tmp)
        os.makedirs(tmp)
        checksums = {}
        for i, payload in enumerate(shards):
            fn = f"shard_{i}.npz"
            path = os.path.join(tmp, fn)
            np.savez(path, **{k: np.asarray(v) for k, v in payload.items()})
            checksums[fn] = _sha256(path)
        for fn, data in blobs.items():
            path = os.path.join(tmp, fn)
            with open(path, "wb") as f:
                f.write(data)
            checksums[fn] = _sha256(path)
        manifest = {"format": FORMAT, "tag": tag, "meta": meta,
                    "files": checksums}
        mpath = os.path.join(tmp, "MANIFEST.json")
        with open(mpath, "w") as f:
            json.dump(manifest, f, indent=1)
            f.flush()
            os.fsync(f.fileno())
        final = self._path(tag)
        if os.path.exists(final):
            shutil.rmtree(final)
        os.replace(tmp, final)
        try:                       # make the rename itself durable
            dfd = os.open(self.dir, os.O_RDONLY)
            os.fsync(dfd)
            os.close(dfd)
        except OSError:
            pass
        self._gc()
        return final

    def _gc(self) -> None:
        for tag in self.tags()[:-self.keep]:
            self.drop(tag)

    # ------------------------------------------------------------------
    def load(self, fingerprint: str | None = None):
        """Newest valid checkpoint as ``(meta, shards, blobs)``, or None."""
        for tag in reversed(self.tags()):
            got = self._load_one(tag, fingerprint)
            if got is not None:
                return got
        return None

    def _load_one(self, tag: int, fingerprint: str | None):
        d = self._path(tag)
        try:
            with open(os.path.join(d, "MANIFEST.json")) as f:
                manifest = json.load(f)
            if manifest.get("format") != FORMAT:
                return None
            meta = manifest["meta"]
            if fingerprint is not None and \
                    meta.get("fingerprint") != fingerprint:
                return None
            for fn, digest in manifest["files"].items():
                if _sha256(os.path.join(d, fn)) != digest:
                    return None
            shards, blobs = [], {}
            for fn in sorted(manifest["files"],
                             key=lambda n: (not n.startswith("shard_"), n)):
                path = os.path.join(d, fn)
                if fn.startswith("shard_") and fn.endswith(".npz"):
                    with np.load(path) as z:
                        shards.append({k: z[k] for k in z.files})
                else:
                    with open(path, "rb") as f:
                        blobs[fn] = f.read()
            return meta, shards, blobs
        except (OSError, ValueError, KeyError, json.JSONDecodeError):
            return None


# ---------------------------------------------------------------------------
# SIGTERM guard (process singleton; chained so outer handlers still run)
# ---------------------------------------------------------------------------
_GUARD = None


def preemption_guard():
    global _GUARD
    if _GUARD is None:
        from repro.train.fault import PreemptionGuard
        _GUARD = PreemptionGuard(chain=True)
    return _GUARD


# ---------------------------------------------------------------------------
# executor-facing wrapper
# ---------------------------------------------------------------------------
class EngineCheckpointer:
    """What the three executors actually talk to.

    * ``maybe_resume(st)`` — restore ``kb`` (dictionary + stores + base)
      from the newest valid checkpoint; returns the live deltas as
      ``{pred: (n, ar) np rows}`` (possibly empty for a finished run), or
      None when there is nothing to resume.  Sets the stats cursor and
      ``st.extra["resumed_rounds"]``.
    * ``boundary(st, state_fn)`` — call at every committed round/phase
      boundary.  Saves when due (cadence / preemption / ``done``), then
      runs the fault hooks, then honors a pending SIGTERM by exiting 143
      (the save above already made the state durable).  ``state_fn`` is
      lazy: full stores are only pulled to the host when a save actually
      happens.  A save (the pull and the write) is one ``tg.checkpoint``
      host span.

    Disabled (all methods cheap no-ops except the fault hooks) when
    ``REPRO_CKPT_DIR`` is unset or ``enabled=False`` (incremental delta
    calls checkpoint nothing: their lifecycle belongs to the caller)."""

    def __init__(self, kb, mode: str, executor: str,
                 enabled: bool | None = None):
        self.kb = kb
        self.mode = mode
        self.executor = executor
        self.faults = faultinject.get_faults()
        d = ckpt_dir()
        self.enabled = (d is not None if enabled is None
                        else bool(enabled) and d is not None)
        self.mgr = RecoveryManager(d) if self.enabled else None
        self.every = ckpt_every()
        self.fingerprint = kb_fingerprint(kb, mode)
        self.guard = preemption_guard() if self.enabled else None
        self.caps_state = None      # from the checkpoint; executors adopt()
        self.resumed_rounds = 0
        self._last_saved = -1

    # ------------------------------------------------------------------
    def maybe_resume(self, st):
        if not self.enabled:
            return None
        loaded = self.mgr.load(self.fingerprint)
        if loaded is None:
            return None
        meta, shards, blobs = loaded
        kb = self.kb
        kb.dict.load_state(pickle.loads(blobs["dict.pkl"]))
        if "caps.pkl" in blobs:
            self.caps_state = pickle.loads(blobs["caps.pkl"])
        stores, deltas, bases = {}, {}, {}
        for payload in shards:
            for key, arr in payload.items():
                kind, _, pred = key.partition("__")
                bucket = {"store": stores, "delta": deltas,
                          "base": bases}.get(kind)
                if bucket is not None:
                    bucket.setdefault(pred, []).append(arr)
        for pred, parts in stores.items():
            kb.rels[pred] = self._to_relation(pred, parts)
        for pred, parts in bases.items():
            kb.base[pred] = self._to_relation(pred, parts)
        st.rounds = int(meta["rounds"])
        st.triggers = int(meta["triggers"])
        st.derived = int(meta["derived"])
        st.extra["resumed_rounds"] = st.rounds
        st.extra["resumed_from"] = (meta.get("executor"),
                                    int(meta.get("ndev", 1)))
        self.resumed_rounds = st.rounds
        self._last_saved = st.rounds
        out = {}
        for pred, parts in deltas.items():
            rows = self._gather(parts)
            if len(rows):
                out[pred] = rows
        return out

    def _gather(self, parts) -> np.ndarray:
        parts = [np.asarray(p) for p in parts if np.asarray(p).size]
        if not parts:
            return np.zeros((0, 1), self.kb.dict.id_dtype)
        rows = parts[0] if len(parts) == 1 else np.concatenate(parts)
        if len(rows):
            # re-establish the global lex order unconditionally: payloads
            # may be per-shard sorted (cross-shard gather) or, for the
            # unsorted-store two-phase executor, in insertion order
            rows = np.ascontiguousarray(rows[np.lexsort(rows.T[::-1])])
        return rows

    def _to_relation(self, pred, parts) -> Relation:
        rows = self._gather(parts)
        ar = max(self.kb.arities.get(pred, rows.shape[1]), 1)
        if rows.shape[1] != ar:
            rows = rows.reshape(-1, ar)
        return Relation.from_numpy(rows, sorted_by=lex_order(ar),
                                   dtype=self.kb.dict.id_dtype)

    # ------------------------------------------------------------------
    def boundary(self, st, state_fn=None, caps=None, done: bool = False):
        preempt = self.guard.requested if self.guard is not None else False
        if (self.enabled and state_fn is not None
                and st.rounds > self._last_saved
                and (done or preempt
                     or st.rounds - self._last_saved >= self.every)):
            with TraceAnnotation("tg.checkpoint"):
                self._save(st, state_fn(), caps, done=done)
        self.faults.on_boundary(st.rounds)
        if preempt:
            raise SystemExit(143)

    def final(self, st, state_fn=None, caps=None):
        """Terminal boundary: persists the converged state (empty deltas,
        ``done`` meta) so resuming a finished run is a no-op."""
        self.boundary(st, state_fn, caps=caps, done=True)

    def _save(self, st, shards, caps, done: bool):
        meta = {"fingerprint": self.fingerprint, "executor": self.executor,
                "mode": self.mode, "rounds": st.rounds,
                "triggers": st.triggers, "derived": st.derived,
                "ndev": len(shards), "done": bool(done)}
        blobs = {"dict.pkl": pickle.dumps(
            self.kb.dict.state_dict(), protocol=pickle.HIGHEST_PROTOCOL)}
        if caps is not None:
            blobs["caps.pkl"] = pickle.dumps(
                caps.state(), protocol=pickle.HIGHEST_PROTOCOL)
        path = self.mgr.save(st.rounds, meta, shards, blobs)
        self._last_saved = st.rounds
        st.extra["checkpoints"] = st.extra.get("checkpoints", 0) + 1
        self.faults.on_checkpoint(path, st.rounds)
