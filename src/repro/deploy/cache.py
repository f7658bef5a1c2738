"""Persistent, content-addressed MDM plan cache.

Planning a whole checkpoint is a one-off deployment cost, but it is paid
again on every engine restart unless the plans persist.  Each layer's
plan is content-addressed by (weight bytes, crossbar spec, mode, format
version): redeploying an unchanged checkpoint is a pure cache read
(~free), while any change to the weights, the device spec or the
deployment mode changes the key and forces a replan — there is no
staleness to manage.

Plans are stored one file per key under a two-level fan-out directory
in a fixed binary layout (17-byte header: flags, version, padding,
ti/tn/rows as u32-LE; then row_perm+row_position in the smallest uint
dtype that holds ``rows``, the two NF grids as f32, and the f32 scale).
A hit is one ``read()`` plus ``np.frombuffer`` views — zip-based
``.npz`` costs ~10ms of zipfile bookkeeping per entry and even raw
``.npy`` records pay a Python header parse each, which together
dominate a whole-model cache hit.  Loaded plans keep numpy leaves —
consumers touch them through jnp ops, which transfer on first use — so
a full-model cache hit does no device work at all.  The
default root is the checkout's ``.mdm_plan_cache/``
(:func:`repro.cachedirs.plan_cache_dir`).  Writes are atomic (tmp +
``os.replace``), so a crash mid-write never corrupts an entry.

On top of the per-entry store, a **per-checkpoint manifest** packs a
whole model's plan set into one file keyed by the full ``{name: key}``
mapping (:func:`manifest_key`): an unchanged-checkpoint redeploy then
resolves every plan with a single read instead of one open per matrix.
Manifests are a read-path accelerator only — entries remain the source
of truth and any manifest mismatch falls back to per-entry probes.
"""
from __future__ import annotations

import dataclasses
import hashlib
import json
import os
import tempfile
import threading

import numpy as np

from repro import telemetry as tm
from repro.cachedirs import plan_cache_dir
from repro.core.mdm import MdmPlan
from repro.core.tiling import CrossbarSpec

# Host-boundary cache telemetry (docs/observability.md): mirrors the
# per-instance CacheStats onto the process-global registry so a serving
# deployment's cache traffic is scrapeable without plumbing the stats
# object out.  All record calls are no-ops while telemetry is disabled.
_M_PROBES = tm.counter(
    "repro_plan_cache_probes_total",
    "Plan-cache entry probes by result (hit/miss).", labels=("result",))
_M_MANIFEST_PROBES = tm.counter(
    "repro_plan_cache_manifest_probes_total",
    "Whole-checkpoint manifest probes by result (hit/miss).",
    labels=("result",))
_M_PUTS = tm.counter(
    "repro_plan_cache_puts_total", "Plan entries written.")
_M_READ_BYTES = tm.counter(
    "repro_plan_cache_read_bytes_total",
    "Bytes read by plan-cache hits (entries and manifests).")

# Bump when the MdmPlan layout or planning semantics change: old
# entries become unreachable (different keys) instead of wrongly hit.
PLAN_CACHE_VERSION = 1


def weight_fingerprint(w) -> str:
    """blake2b over the raw weight bytes + shape + dtype.

    blake2b digests ~2x faster than sha256 on large buffers, the array
    buffer is hashed zero-copy, and hashing releases the GIL — the
    fingerprint pass is most of a whole-model cache hit's cost, and the
    fused planner runs it from a thread pool.
    """
    arr = np.asarray(w)
    if not arr.flags.c_contiguous:
        arr = np.ascontiguousarray(arr)
    h = hashlib.blake2b(digest_size=32)
    h.update(repr((arr.shape, str(arr.dtype))).encode())
    h.update(arr.data)
    return h.hexdigest()


def plan_key(w_fingerprint: str, spec: CrossbarSpec, mode: str,
             fault_fingerprint: str | None = None) -> str:
    """Content address of one layer's plan.

    ``mode`` is the pipeline's cache token
    (:meth:`repro.mapping.MappingPipeline.cache_token`): the historical
    mode string for the canonical legacy pipelines — so shim-resolved
    deployments hit pre-redesign entries — and a ``"pipe:..."``
    strategy fingerprint otherwise.  ``fault_fingerprint`` (a
    :func:`weight_fingerprint` of the physical fault map) enters the
    key when the pipeline's row pass consumes fault maps — a changed
    fault map must invalidate the plan exactly like changed weights do.
    """
    payload = {
        "version": PLAN_CACHE_VERSION,
        "weights": w_fingerprint,
        "spec": list(spec),
        "mode": mode,
    }
    if fault_fingerprint is not None:
        payload["faults"] = fault_fingerprint
    return hashlib.sha256(
        json.dumps(payload, sort_keys=True).encode()).hexdigest()


def manifest_key(keys) -> str:
    """Content address of a whole checkpoint's plan set.

    Derived from the full ``{name: plan_key}`` mapping, so any change to
    any matrix's weights / spec / mode / fault map — or to the set of
    matrix names — changes the manifest key and the stale manifest
    simply becomes unreachable (same no-staleness property as the
    per-entry keys).
    """
    payload = json.dumps(sorted(dict(keys).items()))
    return hashlib.sha256(payload.encode()).hexdigest()


@dataclasses.dataclass
class CacheStats:
    hits: int = 0
    misses: int = 0
    puts: int = 0
    manifest_hits: int = 0
    manifest_misses: int = 0


class PlanCache:
    """Filesystem-backed MdmPlan store keyed by :func:`plan_key`.

    ``get``/``put`` are thread-safe (the fused planner probes entries
    from a thread pool); only the stats counters need the lock — file
    writes are already atomic via tmp + rename.
    """

    def __init__(self, root: str | None = None):
        self.root = root or plan_cache_dir()
        self.stats = CacheStats()
        self._lock = threading.Lock()

    def _path(self, key: str) -> str:
        return os.path.join(self.root, key[:2], key + ".mdmplan")

    def _manifest_path(self, mkey: str) -> str:
        return os.path.join(self.root, "manifest", mkey[:2],
                            mkey + ".mdmmanifest")

    @staticmethod
    def _perm_dtype(rows: int):
        # Permutation entries are < rows: the compact dtype cuts the
        # bytes a whole-model cache hit reads by up to 4x.
        return (np.uint8 if rows <= 256 else
                np.uint16 if rows <= 65536 else np.uint32)

    @classmethod
    def _encode_plan(cls, plan: MdmPlan) -> bytes:
        # Flags bit 0: reversed dataflow; bit 1: column-permuted plan
        # (a trailing [cols u4 header field + col_perm/col_position
        # block] follows the NF block).  Legacy entries have flags in
        # {0, 1} and no col block, so the format stays self-describing
        # at PLAN_CACHE_VERSION 1 and pre-pipeline entries still hit.
        perm = np.asarray(plan.row_perm)
        ti, tn, rows = perm.shape
        perm_dt = cls._perm_dtype(rows)
        has_cols = plan.col_perm is not None
        flags = int(bool(plan.reversed_dataflow)) | (2 if has_cols else 0)
        parts = [
            bytes([flags, PLAN_CACHE_VERSION, 0, 0, 0]),
            np.asarray([ti, tn, rows], "<u4").tobytes(),
        ]
        if has_cols:
            cols = np.asarray(plan.col_perm).shape[-1]
            parts.append(np.asarray([cols], "<u4").tobytes())
        parts += [
            np.stack([perm, np.asarray(plan.row_position)]
                     ).astype(perm_dt).tobytes(),
            np.concatenate([
                np.asarray(plan.nf_before, np.float32).ravel(),
                np.asarray(plan.nf_after, np.float32).ravel(),
                np.asarray(plan.scale, np.float32).reshape(1),
            ]).astype("<f4").tobytes(),
        ]
        if has_cols:
            parts.append(np.stack([np.asarray(plan.col_perm),
                                   np.asarray(plan.col_position)]
                                  ).astype(cls._perm_dtype(cols)).tobytes())
        return b"".join(parts)

    @classmethod
    def _decode_plan(cls, buf: bytes) -> MdmPlan:
        if len(buf) < 17 or buf[1] != PLAN_CACHE_VERSION:
            raise ValueError("bad plan entry header")
        flags = buf[0]
        has_cols = bool(flags & 2)
        ti, tn, rows = np.frombuffer(buf, "<u4", 3, offset=5)
        ti, tn, rows = int(ti), int(tn), int(rows)
        off = 17
        cols = 0
        if has_cols:
            cols = int(np.frombuffer(buf, "<u4", 1, offset=off)[0])
            off += 4
        perm_dt = cls._perm_dtype(rows)
        n_perm = 2 * ti * tn * rows
        perms = np.frombuffer(buf, perm_dt, n_perm, offset=off)
        off += n_perm * perms.itemsize
        nfs = np.frombuffer(buf, "<f4", 2 * ti * tn + 1, offset=off)
        off += nfs.size * 4
        col_perm = col_position = None
        if has_cols:
            col_dt = cls._perm_dtype(cols)
            cperms = np.frombuffer(buf, col_dt, 2 * ti * tn * cols,
                                   offset=off)
            off += cperms.size * cperms.itemsize
            cperms = cperms.astype(np.int32).reshape(2, ti, tn, cols)
            col_perm, col_position = cperms[0], cperms[1]
        if off != len(buf):
            # Exact-length contract: a short buffer already fails one of
            # the frombuffer reads above, but an entry whose header
            # promises more than its body holds (torn write on a
            # non-atomic filesystem, manual corruption) — or one with
            # trailing garbage — must be a miss, not a silent partial
            # decode.
            raise ValueError("plan entry length mismatch")
        perms = perms.astype(np.int32).reshape(2, ti, tn, rows)
        return MdmPlan(
            row_perm=perms[0], row_position=perms[1],
            reversed_dataflow=np.bool_(flags & 1),
            nf_before=nfs[:ti * tn].reshape(ti, tn),
            nf_after=nfs[ti * tn:2 * ti * tn].reshape(ti, tn),
            scale=np.float32(nfs[-1]),
            col_perm=col_perm, col_position=col_position)

    def get(self, key: str) -> MdmPlan | None:
        try:
            with open(self._path(key), "rb") as f:
                buf = f.read()
            plan = self._decode_plan(buf)
        except (FileNotFoundError, ValueError, OSError):
            with self._lock:
                self.stats.misses += 1
            _M_PROBES.labels(result="miss").inc()
            return None
        with self._lock:
            self.stats.hits += 1
        _M_PROBES.labels(result="hit").inc()
        _M_READ_BYTES.inc(len(buf))
        return plan

    def put(self, key: str, plan: MdmPlan) -> None:
        if not self._atomic_write(self._path(key),
                                  self._encode_plan(plan)):
            return
        with self._lock:
            self.stats.puts += 1
        _M_PUTS.inc()

    def _atomic_write(self, path: str, payload: bytes) -> bool:
        try:
            os.makedirs(os.path.dirname(path), exist_ok=True)
            fd, tmp = tempfile.mkstemp(dir=os.path.dirname(path),
                                       suffix=".tmp")
        except OSError:
            return False
        try:
            with os.fdopen(fd, "wb") as f:
                f.write(payload)
                # Durability before visibility: rename-over without
                # fsync can surface a zero-length/truncated entry after
                # a power loss on journaled filesystems — exactly the
                # corruption class ``_decode_plan``'s length check turns
                # into a miss, but better never to publish it.
                f.flush()
                os.fsync(f.fileno())
            os.replace(tmp, path)
        except OSError:
            # Cache is best-effort: a full/read-only disk must not fail
            # the deployment itself.
            try:
                os.unlink(tmp)
            except OSError:
                pass
            return False
        return True

    # ------------------------ checkpoint manifests ---------------------
    #
    # A whole checkpoint's plans in ONE file: header line of JSON
    # entry descriptors (name, per-entry key, offset, length), then the
    # concatenated per-entry binary blobs (the exact bytes the entry
    # files hold).  A full-checkpoint cache hit becomes a single read()
    # + frombuffer views instead of one file open per matrix — the
    # entry-probe pass is the whole cost of a hit redeploy.  Per-entry
    # files are still written (they are shared across checkpoints that
    # have matrices in common); the manifest is a pure read-path
    # accelerator, validated against the caller's expected keys and
    # falling back to per-entry probes on any mismatch or corruption.

    def get_manifest(self, keys) -> dict[str, MdmPlan] | None:
        """Resolve a whole ``{name: key}`` plan set from one file read.

        Returns the full ``{name: MdmPlan}`` mapping, or None if the
        manifest is absent, corrupt, or does not cover exactly the
        requested entries (the caller then falls back to per-entry
        probes).
        """
        keys = dict(keys)
        try:
            with open(self._manifest_path(manifest_key(keys)),
                      "rb") as f:
                buf = f.read()
            nl = buf.index(b"\n")
            head = json.loads(buf[:nl])
            if head.get("v") != PLAN_CACHE_VERSION:
                raise ValueError("manifest version mismatch")
            entries = head["entries"]
            if {e[0]: e[1] for e in entries} != keys:
                raise ValueError("manifest entry set mismatch")
            base = nl + 1
            plans = {name: self._decode_plan(buf[base + off:
                                                base + off + length])
                     for name, _, off, length in entries}
        except (FileNotFoundError, ValueError, KeyError, OSError):
            with self._lock:
                self.stats.manifest_misses += 1
            _M_MANIFEST_PROBES.labels(result="miss").inc()
            return None
        with self._lock:
            self.stats.manifest_hits += 1
        _M_MANIFEST_PROBES.labels(result="hit").inc()
        _M_READ_BYTES.inc(len(buf))
        return plans

    def put_manifest(self, keys, plans) -> None:
        """Write the one-read manifest for a ``{name: key}`` plan set."""
        keys = dict(keys)
        blobs, entries, off = [], [], 0
        for name, key in keys.items():
            blob = self._encode_plan(plans[name])
            entries.append([name, key, off, len(blob)])
            blobs.append(blob)
            off += len(blob)
        head = json.dumps({"v": PLAN_CACHE_VERSION,
                           "entries": entries}).encode() + b"\n"
        self._atomic_write(self._manifest_path(manifest_key(keys)),
                           head + b"".join(blobs))
