"""The system under test, built from a configuration file.

Everything here is the program's own: the BlobSeer service and client, the
checkpointer, the corpus writer and reader, the model and its jitted
train step, wired as ``repro.launch.train.main`` wires them.  What the
benchmark adds is the seed: the corpus and the initial state come from
``bench.gen``.
"""

from __future__ import annotations

from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import BlobCheckpointer
from repro.checkpoint.blobckpt import header_pages_for
from repro.core import BlobSeerService, collect_garbage
from repro.data import CorpusWriter, ShardedReader
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepBuilder

from bench import gen
from bench.families import family

CORPUS_APPEND_TOKENS = 1 << 16   # tokens per corpus append


@dataclass
class System:
    svc: BlobSeerService
    client: Any
    ckpt: BlobCheckpointer
    abstract: Any          # ShapeDtypeStructs of what the blob holds
    shardings: Any
    step_fn: Any
    batch_abs: Any         # ShapeDtypeStructs of one batch
    reader: ShardedReader
    state: Optional[Any]

    def gc_round(self) -> dict:
        return collect_garbage(self.svc, orphan_grace=None)

    def reopen(self) -> None:
        """A restarted trainer's own client, and a checkpointer on the same
        lineage, opened as ``train.main`` opens them: nothing the old
        client cached carries over."""
        self.client = self.svc.client("trainer")
        self.ckpt = BlobCheckpointer(self.client, self.ckpt.blob_id, psize=self.ckpt.psize,
                                     header_pages=self.ckpt.header_bytes // self.ckpt.psize)

    def reader_at(self, state: Optional[dict]) -> ShardedReader:
        """The corpus reader again, at a saved cursor."""
        r = self.reader
        return ShardedReader(self.client, r.blob_id, batch=r.batch, seq_len=r.seq_len,
                             state=state)


def build(cfg: dict, seed: int, corpus_tokens: int = 0) -> System:
    """Service, checkpoint lineage, the step, the corpus and the reader;
    the state made from ``seed``."""
    store = cfg["store"]
    svc = BlobSeerService(n_providers=store["providers"],
                          n_meta_shards=store["meta_shards"],
                          data_replication=store["replication"])
    client = svc.client("trainer")
    model = build_model(family(cfg).model_config(cfg))
    mesh = make_mesh((1, 1), ("data", "model"))
    builder = TrainStepBuilder(model, mesh, strategy="tp",
                               opt=AdamWConfig(**cfg.get("optimizer", {})),
                               remat_policy="none", accum=1)
    abstract_params, axes = model.abstract()
    state_sh = builder.state_shardings(abstract_params, axes)
    psize = store["page_bytes"]
    state_abs = jax.eval_shape(builder.init_state, jax.random.PRNGKey(0))
    ckpt = BlobCheckpointer(client, psize=psize,
                            header_pages=header_pages_for(state_abs, psize))
    client.set_retention(ckpt.blob_id, keep_last=store["keep_last"])
    writer = CorpusWriter(client, psize=psize)
    stream = gen.corpus(seed, corpus_tokens, cfg["vocab_size"])
    for i in range(0, len(stream), CORPUS_APPEND_TOKENS):
        writer.append_tokens(stream[i:i + CORPUS_APPEND_TOKENS])
    batch, seq = cfg["batch"], cfg["seq"]
    reader = ShardedReader(client, writer.blob_id, batch=batch, seq_len=seq)
    batch_abs = {k: jax.ShapeDtypeStruct((batch, seq), jnp.int32)
                 for k in ("tokens", "labels")}
    step_fn = builder.jit_train_step(abstract_params, axes, batch_abs)
    return System(svc, client, ckpt, state_abs, state_sh, step_fn, batch_abs, reader,
                  gen.make_train_state(seed, state_abs, state_sh))
