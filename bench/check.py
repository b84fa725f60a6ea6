"""The comparison that decides ``correct``.

Each number compared has a limit of its own, from the configuration
file's ``limits``.  A number passes when it is at or under its limit; one
the file gives no limit is read but not compared.

* ``loss1_gap``..``loss3_gap``: |program − reference| / |reference| of each
  of the first three steps' loss;
* ``grad1_gap``: by the worst leaf, the gap between the norms of the first
  clipped gradient (the program's read from Adam's first moment after one
  step), against the larger of that leaf's and the median leaf's norm;
* ``change3_gap``: the same for each leaf's change of the fp32 master
  weights over three steps; leaves whose reference gradient is under a
  thousandth of the median leaf's are left out (round-off alone moves them);
* ``save_readback_bytes_differ``: after each GC round, the newest save is
  read back at its version before the next steps change the state: a
  seed-drawn page of every leaf against the same bytes of the state on the
  device, and its manifest's step and reader cursor (one each);
* ``readback_bytes_differ``: bytes of the run's newest checkpoint, read
  back whole at its version after the window, that differ from the state
  on the device (and its step and reader cursor as the manifest holds
  them); in a cell that resumes, one more resume runs outside the window,
  and its restore is the read-back and its state, before its first step,
  the state compared;
* ``resume_bytes_differ``: bytes of that resumed state on the device that
  differ from the newest checkpoint read back at its version leaf by leaf,
  apart from the restore; plus one for each of the restored step and the
  rebuilt reader's cursor that differs from what was saved;
* ``resume_loss_gap``: by the worst resume of the run (set-up's, the
  window's and the check's), |loss of its first step − the loss the same
  step gave without the interruption| / the latter;
* ``digest_pages_differ``: of a seed-drawn sample of pages, the manifest's
  digests (the program's kernels) that differ from the reference digest of
  the bytes read back.
"""

from __future__ import annotations

import functools
import statistics
from typing import Dict, Tuple

import numpy as np

import jax

from bench import gen, reference

DIGEST_SAMPLE_PAGES = 4096
EXCLUDE_BELOW = 1e-3     # of the median leaf's reference gradient norm


def gap_by_worst_leaf(prog: Dict[str, float], ref: Dict[str, float],
                      leaves=None) -> float:
    leaves = sorted(ref) if leaves is None else leaves
    med = statistics.median(ref.values())
    return max(abs(prog[k] - ref[k]) / max(ref[k], med) for k in leaves)


def train_gaps(prog: dict, ref: dict) -> Dict[str, float]:
    """The training numbers, from the program's and the reference's readings."""
    out = {f"loss{i + 1}_gap": abs(p - r) / abs(r)
           for i, (p, r) in enumerate(zip(prog["losses"], ref["losses"]))}
    out["grad1_gap"] = gap_by_worst_leaf(prog["grad1"], ref["grad1"])
    med = statistics.median(ref["grad1"].values())
    moving = [k for k in sorted(ref["grad1"]) if ref["grad1"][k] >= EXCLUDE_BELOW * med]
    out["change3_gap"] = gap_by_worst_leaf(prog["change"], ref["change"], moving)
    return out


def _raw(x) -> np.ndarray:
    return np.ascontiguousarray(np.asarray(x)).reshape(-1).view(np.uint8)


def readback(job, seed: int, page_bytes: int, read=None) -> Tuple[int, int]:
    """(bytes differing, sampled pages whose digest differs) of the newest
    checkpoint against the state on the device.  ``read`` is that
    checkpoint as a resume has just restored it, (state, manifest); without
    it the checkpoint is restored here, at the newest save's version."""
    s = job.sys
    last, reader = job.newest
    restored, manifest = read or s.ckpt.restore(s.abstract, version=last.version,
                                                with_manifest=True)
    differ = 0
    if manifest["step"] != last.step or manifest["extra"].get("reader") != reader:
        differ += 1
    rows = []   # (path, restored bytes) for the digest sample
    for (path, got), (_, want) in zip(gen.leaves_with_paths(restored),
                                      gen.leaves_with_paths(s.state)):
        a, b = _raw(got), _raw(jax.device_get(want))
        differ += (int(np.count_nonzero(a != b)) if a.size == b.size
                   else max(a.size, b.size))
        rows.append((path, a))
    del restored
    return differ, _digest_sample(rows, manifest, seed, page_bytes)


@functools.partial(jax.jit, static_argnums=2)
def _elements(x, start, count: int):
    """``count`` elements of ``x`` (flattened) from ``start``, on the device."""
    return jax.lax.dynamic_slice(x.reshape(-1), (start,), (count,))


def save_sample(job, seed: int) -> int:
    """Bytes that differ between the newest save, read back at its version
    (a seed-drawn page of every leaf), and the state on the device, which
    has not moved since; plus one for each of the manifest's step and
    reader cursor that differs."""
    s = job.sys
    last, reader = job.newest
    manifest, at = s.ckpt.read_manifest(last.version)
    differ = int(manifest["step"] != last.step) + int(manifest["extra"].get("reader") != reader)
    psize = s.ckpt.psize
    recs = {rec["path"]: rec for rec in manifest["leaves"]}
    rng = np.random.default_rng(list(gen.seed_words(seed)) + [3, last.version])
    for path, leaf in gen.leaves_with_paths(s.state):
        item = leaf.dtype.itemsize
        nbytes = leaf.size * item
        rec = recs.get(path)
        if rec is None or rec["nbytes"] != nbytes:
            differ += nbytes
            continue
        size = min(psize, nbytes)
        start = min(int(rng.integers(-(-nbytes // psize))) * psize, nbytes - size)
        got = np.frombuffer(s.client.read(s.ckpt.blob_id, at, rec["offset"] + start, size),
                            np.uint8)
        want = _raw(_elements(leaf, start // item, size // item))
        differ += int(np.count_nonzero(got != want)) if got.size == want.size else size
    return differ


def resumed_state(job, manifest: dict) -> int:
    """Bytes of the state on the device, just resumed from ``manifest``, that
    differ from the newest checkpoint read back at its version one leaf at a
    time (so the host holds one leaf, not a state); plus one for each of the
    restored step and the rebuilt reader's cursor that differs from what was
    saved."""
    s = job.sys
    last, reader = job.newest
    differ = int(manifest["step"] != last.step) + int(s.reader.state_dict() != reader)
    saved, at = s.ckpt.read_manifest(last.version)
    recs = {rec["path"]: rec for rec in saved["leaves"]}
    for path, leaf in gen.leaves_with_paths(s.state):
        nbytes = leaf.size * leaf.dtype.itemsize
        rec = recs.get(path)
        if rec is None or rec["nbytes"] != nbytes:
            differ += nbytes
            continue
        got = np.frombuffer(s.client.read(s.ckpt.blob_id, at, rec["offset"], nbytes), np.uint8)
        differ += int(np.count_nonzero(got != _raw(jax.device_get(leaf))))
    return differ


def _digest_sample(rows, manifest, seed: int, page_bytes: int) -> int:
    counts = [-(-max(a.size, 1) // page_bytes) for _, a in rows]
    total = sum(counts)
    rng = np.random.default_rng(list(gen.seed_words(seed)) + [2])
    picks = np.sort(rng.choice(total, size=min(DIGEST_SAMPLE_PAGES, total), replace=False))
    differ, base = 0, 0
    for (path, a), n in zip(rows, counts):
        mine = picks[(picks >= base) & (picks < base + n)] - base
        base += n
        if not len(mine):
            continue
        saved = np.frombuffer(bytes.fromhex(manifest["digests"][path]),
                              np.uint32).reshape(-1, 2)
        ref = reference.page_digests(a, mine, page_bytes)
        differ += int(np.count_nonzero(np.any(saved[mine] != ref, axis=1)))
    return differ
