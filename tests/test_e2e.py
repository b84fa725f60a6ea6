"""End-to-end: train, kill, restart, resume bit-identically."""

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from repro.checkpoint import BlobCheckpointer
from repro.configs import get_config
from repro.core import BlobSeerService
from repro.data import ByteTokenizer, CorpusWriter, ShardedReader
from repro.launch.mesh import make_mesh
from repro.models import build_model
from repro.train.optimizer import AdamWConfig
from repro.train.step import TrainStepBuilder

STEPS = 16
CKPT_AT = 8


def _setup(svc):
    c = svc.client("trainer")
    tok = ByteTokenizer()
    w = CorpusWriter(c, psize=4096)
    rng = np.random.default_rng(0)
    for i in range(60):
        n = int(rng.integers(40, 120))
        w.append_tokens(tok.encode(f"doc {i}: " + " ".join(
            f"w{int(rng.integers(0, 40))}" for _ in range(n))))
    cfg = get_config("olmo-1b").reduced(vocab_size=tok.vocab_size + 1)
    model = build_model(cfg)
    mesh = make_mesh((1, 1), ("data", "model"))
    builder = TrainStepBuilder(
        model, mesh, strategy="tp",
        opt=AdamWConfig(lr=1e-3, warmup_steps=4, total_steps=STEPS),
        remat_policy="none",
    )
    ap, ax = model.abstract()
    batch_abs = {"tokens": jax.ShapeDtypeStruct((4, 32), jnp.int32),
                 "labels": jax.ShapeDtypeStruct((4, 32), jnp.int32)}
    step = builder.jit_train_step(ap, ax, batch_abs)
    return c, w, model, builder, step


def _run(svc, c, w, builder, step, ckpt, state, reader, lo, hi, losses):
    for s in range(lo, hi):
        tokens, labels = reader.next_batch()
        state, m = step(state, {"tokens": jnp.asarray(tokens),
                                "labels": jnp.asarray(labels)})
        losses.append(float(m["loss"]))
        if s + 1 == CKPT_AT:
            ckpt.save(state, step=s + 1, extra={"reader": reader.state_dict()})
    return state


def test_kill_restart_resumes_bit_identically():
    # ---- uninterrupted reference run ----
    svc = BlobSeerService(n_providers=4, n_meta_shards=2)
    c, w, model, builder, step = _setup(svc)
    ckpt = BlobCheckpointer(c, psize=4096, header_pages=32)
    state = builder.init_state(jax.random.PRNGKey(0))
    reader = ShardedReader(c, w.blob_id, batch=4, seq_len=32)
    ref_losses = []
    state = _run(svc, c, w, builder, step, ckpt, state, reader, 0, STEPS, ref_losses)
    ref_final = jax.tree.leaves(state["params"])[0]

    # ---- interrupted run: train to CKPT_AT, "crash", resume ----
    svc2 = BlobSeerService(n_providers=4, n_meta_shards=2)
    c2, w2, model2, builder2, step2 = _setup(svc2)
    ckpt2 = BlobCheckpointer(c2, psize=4096, header_pages=32)
    state2 = builder2.init_state(jax.random.PRNGKey(0))
    reader2 = ShardedReader(c2, w2.blob_id, batch=4, seq_len=32)
    losses2 = []
    state2 = _run(svc2, c2, w2, builder2, step2, ckpt2, state2, reader2,
                  0, CKPT_AT, losses2)
    del state2, reader2  # crash: in-memory training state lost

    state_abs = jax.eval_shape(lambda r: builder2.init_state(r), jax.random.PRNGKey(0))
    restored, mani = ckpt2.restore(state_abs, with_manifest=True)
    state3 = jax.tree.map(jnp.asarray, restored)
    assert mani["step"] == CKPT_AT
    reader3 = ShardedReader(c2, w2.blob_id, batch=4, seq_len=32,
                            state=mani["extra"]["reader"])
    state3 = _run(svc2, c2, w2, builder2, step2, ckpt2, state3, reader3,
                  CKPT_AT, STEPS, losses2)

    # identical loss trajectory + identical final params
    np.testing.assert_allclose(losses2, ref_losses, rtol=1e-6)
    final2 = jax.tree.leaves(state3["params"])[0]
    np.testing.assert_array_equal(np.asarray(ref_final), np.asarray(final2))


def test_train_main_checkpoint_round_trip():
    from repro.launch import train
    tiny = ["--d-model", "32", "--layers", "2", "--heads", "2", "--d-ff", "64",
            "--seq", "16", "--batch", "2", "--corpus-docs", "20", "--quiet"]
    out = train.main(tiny + ["--steps", "4", "--ckpt-every", "2"])
    assert [st.step for st in out["saves"]] == [2, 4]
    state = out["state"]
    assert jax.tree.leaves(state["params"])[0].dtype == jnp.bfloat16  # arch's own
    like = jax.eval_shape(lambda: state)
    restored = out["ckpt"].restore(like)
    for a, b in zip(jax.tree.leaves(restored), jax.tree.leaves(state)):
        assert a.dtype == b.dtype and a.tobytes() == np.asarray(b).tobytes()
    resave = out["ckpt"].save(state, step=4,
                              extra={"reader": out["reader"].state_dict()})
    assert resave.pages_written == 0

    # two more steps, uninterrupted vs. resumed through main from the store;
    # the second loss reads the restored master weights and Adam moments
    want = []
    for _ in range(2):
        tokens, labels = out["reader"].next_batch()
        state, m = out["step_fn"](state, {"tokens": jnp.asarray(tokens),
                                          "labels": jnp.asarray(labels)})
        want.append(float(m["loss"]))
    again = train.main(tiny + ["--steps", "6", "--resume-blob", out["ckpt_blob"],
                               "--corpus-blob", out["corpus_blob"]],
                       service=out["service"])
    assert again["losses"] == want


def test_generation_runs():
    from repro.launch.serve import generate
    cfg = get_config("olmo-1b").reduced()
    model = build_model(cfg)
    params, _ = model.init(jax.random.PRNGKey(0))
    prompts = [np.asarray([1, 2, 3, 4], np.int32)] * 2
    outs = generate(model, params, prompts, max_new=6, max_len=16)
    assert all(len(o) == 10 for o in outs)
