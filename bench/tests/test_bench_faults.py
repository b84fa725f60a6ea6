"""``correct`` comes out false with the timed path broken, and for the control.

Each test drives a whole tiny run on the CPU (everything but the look for a
chip) with one fault planted underneath.
"""

from __future__ import annotations

import jax
import pytest

from bench import gen, loop, reference, run
from bench.families import family
from bench.tests.tiny import cell_from_files, run_tiny, tiny_cell
from repro.core.blob import BlobClient
from repro.train.step import TrainStepBuilder

TRAIN = ("olmo1b-l4.train-state", "ckpt-every-20")


@pytest.fixture(autouse=True)
def cpu_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def _failed(out) -> list:
    return [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]


def _patch_step(monkeypatch, wrap):
    def jit_train_step(self, *args):
        return jax.jit(wrap(jax.jit(self.train_step_fn())))
    monkeypatch.setattr(TrainStepBuilder, "jit_train_step", jit_train_step)


# what the harness read on this tiny cell at run_tiny's seed before the
# architecture moved into bench/families: the move changes no reading
BEFORE_FAMILIES = {
    "save_readback_bytes_differ": 0, "readback_bytes_differ": 0, "digest_pages_differ": 0,
    "loss1_gap": 1.8033609122212927e-05, "loss2_gap": 1.7812654905115888e-05,
    "grad1_gap": 0.00030623322768642136, "change3_gap": 0.00024696273654990563,
}


def test_sound_run_is_correct():
    cell = tiny_cell(cell_from_files(*TRAIN))
    out = run_tiny(cell)
    assert out["correct"], out["checks"]
    assert {k: c["value"] for k, c in out["checks"].items()} == BEFORE_FAMILIES
    assert family(cell.cfg).train_flops_per_token(cell.cfg, cell.cfg["seq"]) == 737280.0


def test_step_returning_its_state_unchanged(monkeypatch):
    def wrap(step):
        return lambda state, batch: (state, step(state, batch)[1])
    _patch_step(monkeypatch, wrap)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
    assert "change3_gap" in _failed(out)


def test_half_of_the_batch_left_out(monkeypatch):
    def wrap(step):
        def half(state, batch):
            return step(state, {k: v[: v.shape[0] // 2] for k, v in batch.items()})
        return half
    _patch_step(monkeypatch, wrap)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
    assert _failed(out)


def test_loss_altered_where_it_is_produced(monkeypatch):
    def wrap(step):
        def altered(state, batch):
            new, metrics = step(state, batch)
            return new, dict(metrics, loss=metrics["loss"] * 1.05)
        return altered
    _patch_step(monkeypatch, wrap)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
    assert "loss1_gap" in _failed(out)


def test_checkpoint_byte_altered_where_it_is_written(monkeypatch):
    orig = BlobClient.write_many

    def write_many(self, blob_id, writes, *a, **kw):
        (buf, off), *rest = writes
        buf = bytes([buf[0] ^ 0xFF]) + buf[1:]
        return orig(self, blob_id, [(buf, off)] + rest, *a, **kw)
    monkeypatch.setattr(BlobClient, "write_many", write_many)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
    assert "readback_bytes_differ" in _failed(out)


def test_saved_pages_altered_are_caught_after_each_gc_round(monkeypatch):
    orig = BlobClient.write_many

    def write_many(self, blob_id, writes, *a, **kw):
        flipped = [(bytes(b ^ 0x5A for b in buf), off) for buf, off in writes]
        return orig(self, blob_id, flipped, *a, **kw)
    monkeypatch.setattr(BlobClient, "write_many", write_many)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
    assert "save_readback_bytes_differ" in _failed(out)


def test_control_fp8_training_step_is_not_correct(monkeypatch):
    """The reference in float8, put in the program's place, fails a number."""
    def first_steps(self, n):
        stream = gen.corpus(self.seed, self.traffic["corpus_tokens"], self.cfg["vocab_size"])
        batches = gen.batches(stream, self.cfg["batch"], self.cfg["seq"], n)
        self.first = reference.train_numbers(self.cfg, self.seed, batches, "fp8")
        self.steps_done += n
    monkeypatch.setattr(loop.Job, "op_first_steps", first_steps)
    out = run_tiny(tiny_cell(cell_from_files(*TRAIN)))
    assert not out["correct"]
