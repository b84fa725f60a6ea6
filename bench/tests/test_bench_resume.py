"""The resume cell on the CPU: a sound run reads 0, and a fault planted in
the resume path makes ``correct`` come out false."""

from __future__ import annotations

import jax
import numpy as np
import pytest

from bench import run
from bench.tests.tiny import run_tiny, tiny_cell
from repro.checkpoint import BlobCheckpointer
from repro.data import ShardedReader

RESUME = "olmo1b-train.resume"


@pytest.fixture(autouse=True)
def cpu_kernels(monkeypatch):
    monkeypatch.setenv("REPRO_PALLAS", "interpret")
    monkeypatch.setattr(run, "enable_cache", lambda: None)


def _cursor_one_batch_off(monkeypatch):
    """The reader rebuilt at a saved cursor starts one batch late."""
    orig = ShardedReader.__init__

    def init(self, *args, state=None, **kwargs):
        orig(self, *args, state=state, **kwargs)
        if state is not None:
            self.state.position += self._window() * self.state.n_shards
    monkeypatch.setattr(ShardedReader, "__init__", init)


def _restored_byte_flipped(monkeypatch):
    """One byte of the first leaf a restore returns is flipped."""
    orig = BlobCheckpointer.restore

    def restore(self, like, version=None, with_manifest=False):
        tree, manifest = orig(self, like, version, with_manifest=True)
        leaves, treedef = jax.tree.flatten(tree)
        first = np.array(leaves[0])
        first.reshape(-1).view(np.uint8)[0] ^= 0xFF
        tree = jax.tree.unflatten(treedef, [first, *leaves[1:]])
        return (tree, manifest) if with_manifest else tree
    monkeypatch.setattr(BlobCheckpointer, "restore", restore)


FAULTS = {"none": None, "cursor_one_batch_off": _cursor_one_batch_off,
          "restored_byte_flipped": _restored_byte_flipped}
CAUGHT_BY = {"cursor_one_batch_off": "resume_loss_gap",
             "restored_byte_flipped": "resume_bytes_differ"}


@pytest.mark.parametrize("fault", sorted(FAULTS))
def test_resume(monkeypatch, fault):
    if FAULTS[fault] is not None:
        FAULTS[fault](monkeypatch)
    out = run_tiny(tiny_cell(run.load_cell(RESUME)))
    checks = {k: c["value"] for k, c in out["checks"].items()}
    failed = [k for k, c in out["checks"].items() if not c["value"] <= c["limit"]]
    if fault == "none":
        assert out["correct"], out["checks"]
        assert checks["resume_bytes_differ"] == 0 and checks["resume_loss_gap"] == 0
        assert out["metrics"]["resume_s"]["value"] > 0
    else:
        assert not out["correct"]
        assert CAUGHT_BY[fault] in failed, out["checks"]
