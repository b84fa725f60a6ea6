"""End-to-end training driver.

Wires every subsystem together: a BlobSeer deployment provides both the
tokenized corpus (append-ingested, snapshot-pinned readers) and the
versioned incremental checkpoint lineage; the model/optimizer run under
a mesh with logical-rule sharding.

Designed to be killed and restarted at any point: on startup it
GET_RECENTs the checkpoint blob and resumes (params, optimizer, step,
data cursor) bit-identically — the fault-tolerance story of DESIGN.md §5
exercised for real by ``tests/test_e2e.py`` and
``examples/train_e2e.py``.

The model is the arch's own published config: only the width flags
that are given (``--layers``, ``--d-model``, ``--heads``, ``--d-ff``)
and the byte tokenizer's vocabulary replace its figures.

Usage (tiny CPU scale; omit the width flags for the published widths)::

    PYTHONPATH=src python -m repro.launch.train --arch olmo-1b --steps 50 \
        --d-model 128 --layers 2 --heads 4 --d-ff 256 --seq 64 --batch 8
"""

from __future__ import annotations

import argparse
import dataclasses
import time
from pathlib import Path
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from repro.checkpoint import BlobCheckpointer
from repro.checkpoint.blobckpt import header_pages_for
from repro.configs import ARCH_IDS, get_config
from repro.core import BlobSeerService
from repro.data import ByteTokenizer, CorpusWriter, ShardedReader
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.models.config import ModelConfig
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepBuilder

CKPT_PSIZE = 16 * 1024
# the checkout root: src/repro/launch/train.py -> ../../..
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> None:
    """Keep compiled programs across runs.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    nothing is set here; otherwise the cache lives at a fixed path in the
    checkout, since the path is part of what a later run looks up.
    """
    if not jax.config.jax_compilation_cache_dir:
        jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))


def synthesize_corpus(writer: CorpusWriter, tok: ByteTokenizer, n_docs: int,
                      seed: int = 0) -> None:
    """Deterministic synthetic text corpus (number facts + noise)."""
    rng = np.random.default_rng(seed)
    for i in range(n_docs):
        n = int(rng.integers(40, 200))
        words = [f"tok{int(rng.integers(0, 50))}" for _ in range(n // 4)]
        text = f"document {i}: " + " ".join(words)
        writer.append_tokens(tok.encode(text))


def model_config(args, vocab_size: int) -> ModelConfig:
    """The arch's config with only the given width flags and the vocabulary."""
    cfg = get_config(args.arch)
    over = {"vocab_size": vocab_size}
    if args.layers is not None:
        over["n_layers"] = args.layers
    if args.d_model is not None:
        over["d_model"] = args.d_model
    if args.heads is not None:
        over["n_heads"] = args.heads
        over["n_kv_heads"] = min(args.heads, cfg.n_kv_heads)
    if args.d_model is not None or args.heads is not None:
        over["d_head"] = (over.get("d_model", cfg.d_model)
                          // over.get("n_heads", cfg.n_heads))
    if args.d_ff is not None and cfg.d_ff:
        over["d_ff"] = args.d_ff
    return dataclasses.replace(cfg, **over)


def main(argv=None, service: Optional[BlobSeerService] = None) -> dict:
    """Train (resuming from ``--resume-blob`` when it holds a checkpoint).

    ``service``: an existing deployment to train against, so that a
    later call can resume from the blobs an earlier one wrote; by default
    one is built from the flags.
    """
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="olmo-1b", choices=ARCH_IDS)
    ap.add_argument("--steps", type=int, default=50)
    ap.add_argument("--seq", type=int, default=64)
    ap.add_argument("--batch", type=int, default=8)
    ap.add_argument("--accum", type=int, default=1)
    ap.add_argument("--d-model", type=int, default=None)
    ap.add_argument("--layers", type=int, default=None)
    ap.add_argument("--heads", type=int, default=None)
    ap.add_argument("--d-ff", type=int, default=None)
    ap.add_argument("--lr", type=float, default=1e-3)
    ap.add_argument("--ckpt-every", type=int, default=20)
    ap.add_argument("--providers", type=int, default=4)
    ap.add_argument("--replication", type=int, default=1)
    ap.add_argument("--spool", default=None)
    ap.add_argument("--mesh", default="1x1")
    ap.add_argument("--strategy", default="tp")
    ap.add_argument("--corpus-docs", type=int, default=200)
    ap.add_argument("--resume-blob", default=None)
    ap.add_argument("--corpus-blob", default=None)
    ap.add_argument("--quiet", action="store_true")
    args = ap.parse_args(argv)
    enable_compile_cache()

    tok = ByteTokenizer()
    cfg = model_config(args, tok.vocab_size)
    if not args.quiet:
        print(f"[config] {cfg.name}: {cfg.n_layers}L d_model={cfg.d_model} "
              f"heads={cfg.n_heads}x{cfg.head_dim} kv={cfg.n_kv_heads} "
              f"d_ff={cfg.d_ff} vocab={cfg.vocab_size} {cfg.dtype}")
    svc = service
    if svc is None:
        svc = BlobSeerService(
            n_providers=args.providers, n_meta_shards=4,
            data_replication=args.replication, spool_dir=args.spool,
            wal_path=(args.spool + "/vm.wal") if args.spool else None,
        )
    client = svc.client("trainer")

    # ---- corpus (ingestion substrate) ----
    writer = CorpusWriter(client, args.corpus_blob, psize=16 * 1024)
    if args.corpus_blob is None:
        synthesize_corpus(writer, tok, args.corpus_docs)

    # ---- model + step ----
    d0, d1 = (int(x) for x in args.mesh.split("x"))
    mesh = make_mesh((d0, d1), ("data", "model"))
    model = build_model(cfg)
    builder = TrainStepBuilder(
        model, mesh, strategy=args.strategy,
        opt=AdamWConfig(lr=args.lr, warmup_steps=10, total_steps=args.steps),
        remat_policy="none", accum=args.accum,
    )
    abstract_params, axes_tree = model.abstract()
    state_sh = builder.state_shardings(abstract_params, axes_tree)

    # ---- checkpoint lineage (resume if one exists) ----
    state_abs = jax.eval_shape(builder.init_state, jax.random.PRNGKey(0))
    ckpt = BlobCheckpointer(client, args.resume_blob, psize=CKPT_PSIZE,
                            header_pages=header_pages_for(state_abs, CKPT_PSIZE))
    start_step = 0
    reader_state = None
    try:
        restored, manifest = ckpt.restore(state_abs, with_manifest=True)
    except FileNotFoundError:  # no checkpoint committed in this lineage yet
        state = jax.jit(builder.init_state, out_shardings=state_sh)(
            jax.random.PRNGKey(0))
    else:
        state = jax.device_put(restored, state_sh)
        del restored
        ckpt.load_digest_cache()
        start_step = manifest["step"]
        reader_state = manifest["extra"].get("reader")
        if not args.quiet:
            print(f"[resume] blob={ckpt.blob_id} step={start_step}")

    reader = ShardedReader(client, writer.blob_id, batch=args.batch,
                           seq_len=args.seq, state=reader_state)

    batch_abs = {
        "tokens": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
        "labels": jax.ShapeDtypeStruct((args.batch, args.seq), jnp.int32),
    }
    step_fn = builder.jit_train_step(abstract_params, axes_tree, batch_abs)

    # ---- loop ----
    losses, step_s, saves, save_s = [], [], [], []
    t0 = time.time()
    for step in range(start_step, args.steps):
        ts = time.perf_counter()
        tokens, labels = reader.next_batch()
        batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
        state, metrics = step_fn(state, batch)
        losses.append(float(metrics["loss"]))  # waits for the step
        step_s.append(time.perf_counter() - ts)
        if not args.quiet and (step % 10 == 0 or step == args.steps - 1):
            print(f"step {step:5d} loss {losses[-1]:.4f} "
                  f"gnorm {float(metrics['grad_norm']):.3f}")
        if (step + 1) % args.ckpt_every == 0 or step == args.steps - 1:
            ts = time.perf_counter()
            stats = ckpt.save(state, step=step + 1,
                              extra={"reader": reader.state_dict()})
            save_s.append(time.perf_counter() - ts)
            saves.append(stats)
            if not args.quiet:
                print(f"[ckpt] v{stats.version} step {stats.step} "
                      f"wrote {stats.pages_written}/{stats.pages_total} pages "
                      f"(sharing {stats.sharing_fraction:.0%})")
    wall = time.time() - t0
    return {
        "losses": losses, "wall_s": wall, "ckpt_blob": ckpt.blob_id,
        "corpus_blob": writer.blob_id, "final_step": args.steps,
        "service": svc, "client": client, "state": state,
        "ckpt": ckpt, "reader": reader, "step_fn": step_fn,
        "step_s": step_s, "saves": saves, "save_s": save_s,
    }


if __name__ == "__main__":
    out = main()
    print(f"done: {len(out['losses'])} steps in {out['wall_s']:.1f}s, "
          f"final loss {out['losses'][-1]:.4f}")
