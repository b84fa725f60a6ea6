"""Smoke run on the chip: train, checkpoint into BlobSeer, restore, resume.

Drives the trainer's entry point, ``repro.launch.train.main``, at the
published widths of olmo-1b (d_model 2048, 16 heads of 128, SwiGLU d_ff
8192, bf16 parameters with fp32 master weights and Adam moments) through
one ``BlobSeerService``:

1. 6 train steps, with checkpoints at steps 3 and 6;
2. the checkpoint is restored onto the state's shardings;
3. for every leaf, the on-chip page digests must equal the checkpoint's
   manifest and the host twin ``host_page_digest`` of the same bytes,
   and the restored leaf must equal the saved one bit for bit;
4. a re-save of the unchanged state must write 0 data pages;
5. two more steps on the live state give the uninterrupted losses; the
   state is then dropped, ``train.main`` resumes from the store, and its
   two steps must give the same losses (the second reads the restored
   fp32 master weights and Adam moments).

Any failed check exits non-zero.  Lines marked ``[observed]`` are single
observations of one run, not benchmark metrics.  The last line of a
passing run is one JSON object naming the device and the number of chips
the run used.

    python chip_smoke.py              # one chip
    python chip_smoke.py --chips 4    # the same on a 1x4 (tensor-parallel) mesh
"""

from __future__ import annotations

import argparse
import json
import resource
import statistics
import sys
import time
from pathlib import Path

import numpy as np

import jax
import jax.numpy as jnp

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.checkpoint.blobckpt import flatten_with_paths  # noqa: E402
from repro.configs import get_config  # noqa: E402
from repro.core import BlobSeerService  # noqa: E402
from repro.data import ByteTokenizer  # noqa: E402
from repro.kernels import ops as kops  # noqa: E402
from repro.kernels.hostdigest import host_page_digest  # noqa: E402
from repro.launch import train  # noqa: E402

ARCH = "olmo-1b"
LAYERS = 4          # of the published 16
SEQ = 2048
# batch 4 at seq 2048 needs 16.27 GB of the v5e's 15.75 GB of HBM (the
# compiler's memory report for the 4-layer step); batch 2 needs 12.4 GB
BATCH = 2
STEPS, CKPT_EVERY = 6, 3
RESUME_STEPS = 2    # the second one reads the restored optimizer state


class SmokeFailure(Exception):
    pass


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SmokeFailure(what)


def observed(what: str) -> None:
    peak = resource.getrusage(resource.RUSAGE_SELF).ru_maxrss / 2**20
    print(f"[observed] {what} (host peak RSS so far {peak:.3f} GiB)", flush=True)


def _host_bytes(leaf) -> np.ndarray:
    return np.ascontiguousarray(jax.device_get(leaf)).reshape(-1).view(np.uint8)


def run(width_args: list, mesh: str) -> int:
    """All phases; raises SmokeFailure on the first failed check.

    Returns the number of chips the resumed state lives on.
    """
    observed("backend up, nothing allocated yet")
    svc = BlobSeerService(n_providers=4, n_meta_shards=4)
    common = width_args + ["--mesh", mesh, "--quiet"]

    # ---- 1. train with two checkpoints ----
    t = time.perf_counter()
    out = train.main(common + ["--steps", str(STEPS),
                               "--ckpt-every", str(CKPT_EVERY)], service=svc)
    observed(f"train.main {STEPS} steps + saves: {time.perf_counter() - t:.3f} s wall")
    observed(f"first step (compile + run): {out['step_s'][0]:.3f} s; "
             f"later steps median {statistics.median(out['step_s'][1:]):.4f} s")
    for st, s in zip(out["saves"], out["save_s"]):
        observed(f"save step {st.step}: {s:.3f} s, {st.pages_written}/"
                 f"{st.pages_total} pages, {st.written_bytes} bytes written")
    print(f"losses {out['losses']}")
    check(all(np.isfinite(out["losses"])), f"non-finite loss {out['losses']}")
    check([st.step for st in out["saves"]] == [CKPT_EVERY, STEPS],
          f"checkpoints at {[st.step for st in out['saves']]}")
    check(out["saves"][0].pages_written == out["saves"][0].pages_total,
          "first save did not write every page")

    state, ckpt, reader = out.pop("state"), out["ckpt"], out["reader"]
    psz = ckpt.psize
    leaves = flatten_with_paths(state)
    n_bytes = sum(leaf.size * leaf.dtype.itemsize for _, leaf in leaves)
    print(f"state: {len(leaves)} leaves, {n_bytes} bytes, page {psz} B")

    # ---- 2. restore onto the state's shardings ----
    t = time.perf_counter()
    restored = ckpt.restore(jax.eval_shape(lambda: state))
    observed(f"restore (store -> host): {time.perf_counter() - t:.3f} s")
    t = time.perf_counter()
    placed = jax.block_until_ready(jax.device_put(
        restored, jax.tree.map(lambda x: x.sharding, state)))
    observed(f"restore (host -> device): {time.perf_counter() - t:.3f} s")
    del restored
    placed = [leaf for _, leaf in flatten_with_paths(placed)]

    # ---- 3. per leaf: on-chip digests == manifest == host twin, and the
    #      restored leaf == the saved one, bit for bit ----
    t = time.perf_counter()
    manifest, _ = ckpt.read_manifest()
    for i, (path, leaf) in enumerate(leaves):
        dg = np.asarray(kops.page_digest(leaf, page_bytes=psz))
        saved = np.frombuffer(bytes.fromhex(manifest["digests"][path]), np.uint32)
        check(np.array_equal(dg.reshape(-1), saved),
              f"{path}: kernel digests differ from the manifest's")
        raw = _host_bytes(leaf)
        for p in range(dg.shape[0]):
            twin = host_page_digest(raw[p * psz:(p + 1) * psz].tobytes(), psz)
            check(twin == (int(dg[p, 0]), int(dg[p, 1])),
                  f"{path} page {p}: kernel digest differs from host twin")
        back, placed[i] = placed[i], None
        check(back.sharding == leaf.sharding, f"{path}: restored onto {back.sharding}")
        check(np.array_equal(_host_bytes(back), raw), f"{path}: restored bytes differ")
        del back
    observed(f"digest and restore checks: {time.perf_counter() - t:.3f} s")

    # ---- 4. unchanged state re-saves 0 data pages ----
    t = time.perf_counter()
    st = ckpt.save(state, step=STEPS, extra={"reader": reader.state_dict()})
    observed(f"re-save of the unchanged state: {time.perf_counter() - t:.3f} s, "
             f"{st.pages_written} data pages")
    check(st.pages_written == 0, f"re-save wrote {st.pages_written} data pages")

    # ---- 5. resumed steps == uninterrupted steps; the second step's loss
    #      reads the restored master weights and Adam moments ----
    want = []
    for _ in range(RESUME_STEPS):
        tokens, labels = reader.next_batch()
        state, metrics = out["step_fn"](state, {"tokens": jnp.asarray(tokens),
                                                "labels": jnp.asarray(labels)})
        want.append(float(metrics["loss"]))
    del state, leaves, metrics, out  # drop the whole training state
    t = time.perf_counter()
    resumed = train.main(common + ["--steps", str(STEPS + RESUME_STEPS),
                                   "--resume-blob", ckpt.blob_id,
                                   "--corpus-blob", reader.blob_id], service=svc)
    observed(f"resume call (restore + compile + {RESUME_STEPS} steps + save): "
             f"{time.perf_counter() - t:.3f} s")
    print(f"steps {STEPS + 1}-{STEPS + RESUME_STEPS}: uninterrupted losses "
          f"{want!r}, resumed losses {resumed['losses']!r}")
    check(resumed["losses"] == want, "resumed losses differ from uninterrupted")
    return len(resumed["state"]["step"].sharding.device_set)


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1)
    args = ap.parse_args()

    train.enable_compile_cache()
    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"FAIL: no TPU; JAX found {devices[0].platform}", file=sys.stderr)
        return 1
    if len(devices) < args.chips:
        print(f"FAIL: {args.chips} chips asked for, {len(devices)} found",
              file=sys.stderr)
        return 1

    cfg = get_config(ARCH)
    print(f"[cut] depth: {LAYERS} of {cfg.n_layers} layers")
    print(f"[cut] vocabulary: {ByteTokenizer.vocab_size} (byte tokenizer) "
          f"of {cfg.vocab_size}")
    print(f"[cut] batch: seq {SEQ} x batch {BATCH}")
    width_args = ["--arch", ARCH, "--layers", str(LAYERS),
                  "--seq", str(SEQ), "--batch", str(BATCH)]
    try:
        used = run(width_args, "1x1" if args.chips == 1 else "1x4")
        check(used == args.chips, f"state on {used} chips, not {args.chips}")
    except SmokeFailure as e:
        print(f"FAIL: {e}", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devices[0].platform, "kind": devices[0].device_kind,
        "count": used}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
