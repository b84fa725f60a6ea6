"""The system under test, built from a configuration file.

Everything here is the program's own: the BlobSeer service and client, the
checkpointer, the corpus writer and reader, the model and its jitted
train step, wired as ``repro.launch.train.main`` wires them.  What the
benchmark adds is the seed: the corpus and the initial state come from
``bench.gen``.
"""

from __future__ import annotations

import dataclasses
from dataclasses import dataclass
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.checkpoint import BlobCheckpointer
from repro.checkpoint.blobckpt import header_pages_for
from repro.configs import get_config
from repro.core import BlobSeerService, collect_garbage
from repro.data import CorpusWriter, ShardedReader
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepBuilder

from bench import gen

CORPUS_APPEND_TOKENS = 1 << 16   # tokens per corpus append


def model_config(cfg: dict):
    """The program's model config with the file's sizes; refuses a file the
    program cannot run as stated."""
    base = get_config(cfg["program_arch"])
    heads = cfg["num_attention_heads"]
    mc = dataclasses.replace(
        base, n_layers=cfg["num_hidden_layers"], vocab_size=cfg["vocab_size"],
        d_model=cfg["hidden_size"], n_heads=heads,
        n_kv_heads=cfg["num_key_value_heads"], d_ff=cfg["intermediate_size"],
        d_head=cfg["hidden_size"] // heads, dtype=cfg["dtype"])
    stated = {
        "norm_kind": "nonparam_ln", "mlp_kind": "swiglu" if cfg["hidden_act"] == "silu" else None,
        "tie_embeddings": cfg["tie_word_embeddings"], "rope_theta": cfg["rope_theta"],
        "qkv_bias": cfg["attention_bias"], "qk_norm": False, "window": None,
        "block_pattern": ("attn",), "n_experts": 0,
    }
    for key, want in stated.items():
        if getattr(mc, key) != want:
            raise ValueError(f"program config {key}={getattr(mc, key)!r}, file states {want!r}")
    return mc


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


def build(cfg: dict, seed: int, corpus_tokens: int = 0) -> System:
    """Service, checkpoint lineage, the step, the corpus and the reader;
    the state made from ``seed``."""
    store = cfg["store"]
    svc = BlobSeerService(n_providers=store["providers"],
                          n_meta_shards=store["meta_shards"],
                          data_replication=store["replication"])
    client = svc.client("trainer")
    model = build_model(model_config(cfg))
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
