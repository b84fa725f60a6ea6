"""Versioned, incremental, branchable checkpoints over BlobSeer.

This is the paper's technique deployed as the framework's fault-
tolerance substrate:

* the training state pytree is laid out in one blob, every leaf aligned
  to page boundaries;
* each save WRITEs only the *changed page ranges* (detected with the
  ``page_digest``/``delta_mask`` kernels), so unchanged pages — frozen
  embeddings, cold optimizer slots, the entire model when only the data
  cursor moved — are physically shared between checkpoints via the
  segment tree's copy-on-write weaving (paper §4.3 "efficient use of
  storage space").  All dirty runs of one save ride a single
  ``BlobClient.write_many`` batch: one version per run as before, but
  one version-manager assignment round trip and one batched completion
  for the whole save (the scale-out write plane);
* commit protocol: data pages -> manifest (layout + step + digests +
  pipeline cursor) -> a one-page *commit pointer* holding the manifest
  write's snapshot version.  A restore resolves the pointer and reads
  manifest + leaves **at that version** — BlobSeer snapshots are
  immutable, so a reader can GET_RECENT at any moment (mid-save
  included) and always reconstruct a fully consistent checkpoint, while
  later saves proceed concurrently on higher versions;
* BRANCH forks a checkpoint lineage in O(1) bytes for ablations /
  fine-tunes (examples/branch_experiments.py);
* the delta scan's page digests are passed straight through
  ``write_many(..., digests=...)`` as the dedup-handshake input, so a
  deployment with content-addressed dedup matches equal pages (branch
  twins, re-written checkpoints) without hashing anything twice.

Page digests are taken where each leaf lives: the kernels bitcast the
device-resident leaf in place, and a leaf sharded over a mesh is
digested by every device of that mesh, each over its own share of the
pages.  Blob traffic is plain numpy/bytes on the host side: a leaf with
a dirty page is pulled once with ``jax.device_get`` (a clean leaf never
leaves the device) and all dirty runs of a save ride one batched
``write_many`` (a real multi-host deployment would hand each host its
own leaf shards; the interface is per-leaf so that change is local).
Restored leaves come back as numpy, for the caller to place with its
shardings.
"""

from __future__ import annotations

import json
import zlib
from dataclasses import dataclass
from typing import Any, Dict, List, Optional, Tuple, Union

import numpy as np

import jax

from repro.core.blob import BlobClient
from repro.core.version_manager import RetiredVersion, VersionUnpublished
from repro.kernels import ops as kops
from repro.spans import span


@dataclass
class CheckpointStats:
    version: int
    step: int
    total_bytes: int
    written_bytes: int
    pages_total: int
    pages_written: int
    d2h_bytes: int = 0   # bytes pulled from the device (the dirty leaves)
    pack_copy_bytes: int = 0  # bytes of runs copied to be zero-padded

    @property
    def sharing_fraction(self) -> float:
        return 1.0 - (self.pages_written / max(self.pages_total, 1))


def flatten_with_paths(tree) -> List[Tuple[str, Any]]:
    flat = jax.tree_util.tree_flatten_with_path(tree)[0]
    out = []
    for path, leaf in flat:
        key = "/".join(
            str(p.key) if hasattr(p, "key") else str(p.idx) for p in path
        )
        out.append((key, leaf if hasattr(leaf, "dtype") else np.asarray(leaf)))
    out.sort(key=lambda kv: kv[0])
    return out


def _nbytes(leaf) -> int:
    return int(np.prod(np.shape(leaf))) * np.dtype(leaf.dtype).itemsize


def header_pages_for(like, psize: int) -> int:
    """Header pages (commit pointer + manifest) for a state shaped ``like``.

    The manifest keeps 16 hex digits of digest per page plus one record
    per leaf, so its size follows the page count.  The bound is taken on
    the uncompressed JSON (zlib adds at most a few bytes per 16 KiB
    block), with 4 KiB left for the top-level fields and ``extra``.
    """
    leaves = flatten_with_paths(like)
    n_pages = sum(-(-max(_nbytes(leaf), 1) // psize) for _, leaf in leaves)
    per_leaf = sum(128 + 2 * len(path) + 16 * len(np.shape(leaf))
                   for path, leaf in leaves)
    manifest = 4096 + per_leaf + 16 * n_pages
    record = 8 + manifest + manifest // 1000 + 64
    return 1 + -(-record // psize)


class BlobCheckpointer:
    def __init__(
        self,
        client: BlobClient,
        blob_id: Optional[str] = None,
        *,
        psize: int = 256 * 1024,
        header_pages: int = 64,
    ) -> None:
        self.client = client
        if blob_id is None:
            blob_id = client.create(psize=psize)
        self.blob_id = blob_id
        self.psize = client.vm.psize_of(blob_id)
        self.header_bytes = header_pages * self.psize
        # header layout: [commit pointer page][manifest region]
        self.manifest_off = self.psize
        self._digests: Dict[str, np.ndarray] = {}   # path -> (n_pages, 2) u32
        self._layout: Dict[str, Tuple[int, int]] = {}  # path -> (offset, nbytes)
        # rolling GC pin on the latest commit's manifest snapshot: the
        # commit pointer dereferences an *older* version than the commit
        # write itself, which a keep-last retention window cannot see
        self._manifest_lease: Optional[str] = None

    # ------------------------------------------------------------------- save
    def save(self, state, step: int, extra: Optional[Dict] = None) -> CheckpointStats:
        """Write an incremental checkpoint; returns sharing stats.

        Its phases are spans (``repro.spans``): ``ckpt.save`` around it
        all; per leaf ``ckpt.digest``, and for a dirty leaf ``ckpt.d2h``
        and ``ckpt.pack``; the ``blob.*`` spans of ``write_many``; then
        ``ckpt.commit``.
        """
        with span("ckpt.save"):
            return self._save(state, step, extra)

    def _save(self, state, step: int, extra: Optional[Dict]) -> CheckpointStats:
        leaves = flatten_with_paths(state)
        psz = self.psize

        # -- layout: leaf offsets page-aligned after the header region --
        offset = self.header_bytes
        layout: Dict[str, Tuple[int, int]] = {}
        for path, leaf in leaves:
            nbytes = max(_nbytes(leaf), 1)
            layout[path] = (offset, nbytes)
            offset += -(-nbytes // psz) * psz
        total = offset
        layout_changed = layout != self._layout

        # BlobSeer WRITE forbids holes (offset <= size of the previous
        # snapshot): on first save, commit a zero header so subsequent
        # page-aligned leaf writes extend the blob contiguously.
        recent = self.client.get_recent(self.blob_id)
        cur_size = self.client.get_size(self.blob_id, recent) if recent else 0
        if cur_size < self.header_bytes:
            self.client.write(self.blob_id, b"\0" * self.header_bytes, 0)

        written_bytes = 0
        pages_written = 0
        d2h_bytes = 0
        pack_copy_bytes = 0
        pages_total = (total - self.header_bytes) // psz
        manifest_leaves = []
        new_digests: Dict[str, np.ndarray] = {}
        # dirty page runs across ALL leaves are collected and written as
        # one write_many batch: one version per run (same snapshots as
        # one write() per run), but the whole save pays a single
        # version-manager assignment round trip and a single batched
        # completion — the scale-out write plane under the checkpointer
        dirty_writes: List[Tuple[Union[bytes, memoryview], int]] = []
        # per run, the delta scan's page fingerprints ride along into
        # write_many as the dedup-handshake input — the content-hash
        # index matches on exactly these digests, nothing hashes twice
        dirty_digests: List[List[Tuple[int, int]]] = []
        for path, leaf in leaves:
            off, nbytes = layout[path]
            with span("ckpt.digest"):
                # digest where the leaf lives (its device, or its mesh)
                dg_dev = kops.page_digest(leaf, page_bytes=psz)
                old = self._digests.get(path)
                if layout_changed or old is None or old.shape != dg_dev.shape:
                    dirty = np.ones(dg_dev.shape[0], dtype=bool)
                else:
                    dirty = np.asarray(kops.delta_mask(dg_dev, old))
                dg = np.asarray(dg_dev)
            new_digests[path] = dg
            manifest_leaves.append({
                "path": path,
                "shape": list(np.shape(leaf)),
                "dtype": str(np.dtype(leaf.dtype)),
                "offset": off,
                "nbytes": nbytes,
            })
            if not dirty.any():
                continue  # clean leaf: nothing leaves the device
            with span("ckpt.d2h"):
                raw = np.ascontiguousarray(jax.device_get(leaf)).reshape(-1).view(np.uint8)
            d2h_bytes += _nbytes(leaf)
            with span("ckpt.pack"):
                # write contiguous dirty page runs of full pages: page-aligned
                # writes are BlobSeer's fast path (no boundary merging) and
                # keep blob growth contiguous.  A run goes out as a read-only
                # view of the D2H buffer (the store copies each page out of
                # it once); only a run whose last page passes the leaf's end
                # is copied here, to be zero-padded
                n_pages = dg.shape[0]
                view = memoryview(raw).toreadonly()
                i = 0
                while i < n_pages:
                    if not dirty[i]:
                        i += 1
                        continue
                    j = i
                    while j < n_pages and dirty[j]:
                        j += 1
                    lo, hi = i * psz, j * psz
                    if hi <= raw.size:
                        chunk = view[lo:hi]
                    else:
                        chunk = raw[lo:].tobytes() + b"\0" * (hi - raw.size)
                        pack_copy_bytes += hi - lo
                    dirty_writes.append((chunk, off + lo))
                    dirty_digests.append(
                        [(int(dg[k, 0]), int(dg[k, 1])) for k in range(i, j)])
                    written_bytes += len(chunk)
                    pages_written += j - i
                    i = j

        if dirty_writes:
            self.client.write_many(self.blob_id, dirty_writes,
                                   digests=dirty_digests)

        with span("ckpt.commit"):
            manifest = {
                "format": 1,
                "step": step,
                "total_bytes": total,
                "leaves": manifest_leaves,
                "extra": extra or {},
                "digests": {p: d.tobytes().hex() for p, d in new_digests.items()},
            }
            payload = zlib.compress(json.dumps(manifest).encode())
            record = len(payload).to_bytes(8, "little") + payload
            if len(record) > self.header_bytes - self.manifest_off:
                raise ValueError(
                    f"manifest ({len(record)}B) exceeds header region "
                    f"({self.header_bytes - self.manifest_off}B); raise header_pages"
                )
            # commit protocol: manifest, then the commit pointer naming the
            # manifest write's snapshot version (restores read AT that version)
            vm_version = self.client.write(self.blob_id, record, self.manifest_off)
            self.client.sync(self.blob_id, vm_version)
            # roll the GC pin forward NOW, while the manifest snapshot is
            # still the newest published version (always kept): pinning only
            # after the commit write would leave a window where a retention
            # GC round retires the manifest of the just-committed checkpoint
            lease = self.client.pin(self.blob_id, vm_version)
            try:
                commit = vm_version.to_bytes(8, "little") + b"\1"
                vc = self.client.write(self.blob_id, commit, 0)
                self.client.sync(self.blob_id, vc)
            except BaseException:
                # failed commit: release the just-taken pin or it leaks an
                # untimed lease that excludes this snapshot from GC forever
                try:
                    self.client.unpin(lease)
                except Exception:
                    pass  # best effort (e.g. wire down); save() still fails
                raise
            if self._manifest_lease is not None:
                self.client.unpin(self._manifest_lease)
            self._manifest_lease = lease
        self._digests = new_digests
        self._layout = layout
        written_bytes += len(record) + len(commit)
        return CheckpointStats(
            version=vc, step=step, total_bytes=total,
            written_bytes=written_bytes, pages_total=pages_total,
            pages_written=pages_written, d2h_bytes=d2h_bytes,
            pack_copy_bytes=pack_copy_bytes,
        )

    # ---------------------------------------------------------------- restore
    def read_manifest(self, version: Optional[int] = None) -> Tuple[Dict, int]:
        """(manifest, resolved_version). Leaf reads must use the latter.

        ``version`` may be any snapshot (default: most recent published);
        the commit pointer stored at that snapshot names the manifest
        write's version, and manifest + leaves are read there — immutable
        snapshots make this consistent no matter what later saves did.
        """
        at = version if version is not None else self.client.get_recent(self.blob_id)
        if at == 0:
            raise FileNotFoundError("no checkpoint published yet")
        head = self.client.read(self.blob_id, at, 0, 9)
        if head[8] != 1:
            raise FileNotFoundError("no checkpoint committed yet")
        vm = int.from_bytes(head[:8], "little")
        head = self.client.read(self.blob_id, vm, self.manifest_off, 8)
        n = int.from_bytes(head, "little")
        raw = self.client.read(self.blob_id, vm, self.manifest_off + 8, n)
        manifest = json.loads(zlib.decompress(raw))
        return manifest, vm

    def restore(self, like, version: Optional[int] = None,
                with_manifest: bool = False):
        """Rebuild a state pytree shaped ``like`` from a checkpoint.

        ``like`` may contain arrays or ShapeDtypeStructs; restored leaves
        are plain numpy (callers ``device_put`` with their shardings).

        The commit-pointer snapshot and the resolved manifest snapshot
        are both pinned before their reads, so a concurrent GC round
        (retention-driven snapshot retirement) cannot sweep the
        checkpoint out from under the manifest or leaf reads.  If GC
        retires the snapshot before the pin lands, the pin raises a
        typed ``RetiredVersion`` and the caller can retry at a newer
        commit.
        """
        at = version if version is not None else self.client.get_recent(self.blob_id)
        outer = self.client.pin(self.blob_id, at) if at > 0 else None
        try:
            manifest, version = self.read_manifest(at)
            lease = self.client.pin(self.blob_id, version)
        finally:
            if outer is not None:
                self.client.unpin(outer)
        try:
            by_path = {l["path"]: l for l in manifest["leaves"]}
            flat = jax.tree_util.tree_flatten_with_path(like)
            leaves = []
            for path, leaf in flat[0]:
                key = "/".join(str(p.key) if hasattr(p, "key") else str(p.idx)
                               for p in path)
                rec = by_path.get(key)
                if rec is None:
                    raise KeyError(f"checkpoint v{version} missing leaf {key}")
                raw = self.client.read(self.blob_id, version, rec["offset"], rec["nbytes"])
                arr = np.frombuffer(raw, dtype=np.dtype(rec["dtype"])).reshape(rec["shape"])
                leaves.append(arr)
            tree = jax.tree_util.tree_unflatten(flat[1], leaves)
        finally:
            self.client.unpin(lease)
        if with_manifest:
            return tree, manifest
        return tree

    def load_digest_cache(self, version: Optional[int] = None) -> None:
        """Resume delta-detection after a trainer restart."""
        manifest, _ = self.read_manifest(version)
        self._digests = {
            p: np.frombuffer(bytes.fromhex(h), dtype=np.uint32).reshape(-1, 2)
            for p, h in manifest.get("digests", {}).items()
        }
        self._layout = {
            l["path"]: (l["offset"], l["nbytes"]) for l in manifest["leaves"]
        }

    # ----------------------------------------------------------------- branch
    def branch(self, version: Optional[int] = None) -> "BlobCheckpointer":
        """Fork the lineage at a commit version (default: most recent)."""
        if version is None:
            version = self.client.get_recent(self.blob_id)
        bid = self.client.branch(self.blob_id, version)
        child = BlobCheckpointer(self.client, bid,
                                 header_pages=self.header_bytes // self.psize)
        child.load_digest_cache(version)
        return child

    def steps(self) -> List[Tuple[int, int]]:
        """(version, step) of every complete checkpoint in the lineage."""
        out = []
        recent = self.client.get_recent(self.blob_id)
        seen = set()
        v = recent
        while v > 0:
            try:
                manifest, _ = self.read_manifest(v)
            except (FileNotFoundError, VersionUnpublished, RetiredVersion):
                # Typed end-of-history ONLY: no checkpoint published or
                # committed at v (read_manifest's FileNotFoundError), a
                # never-assigned version, or one GC already retired.
                # Anything else — a downed endpoint, a wire error, real
                # corruption — must propagate: swallowing it here used
                # to silently truncate the listing to whatever prefix
                # happened to be reachable, and callers pruned/restored
                # against that lie.
                break
            key = manifest["step"]
            if key not in seen:
                out.append((v, key))
                seen.add(key)
            # jump to before this checkpoint's writes: heuristic walk
            v -= 1
            if len(out) > 10_000:
                break
        return sorted(out)
