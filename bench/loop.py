"""The one general generator of every traffic mix: a closed loop of operations.

A traffic file lists the operations of set-up and of one cycle.  The
window repeats whole cycles: a cycle that starts before the window's
seconds have passed completes and counts.  Each operation calls the
program as its users do and is timed as a host span of its own name.

Operations:

* ``train_steps`` (``n``): read a batch, run the jitted step, read the
  loss back, as ``repro.launch.train.main``'s loop does;
* ``first_steps`` (``n``): the same, recording what the correctness check
  compares (each loss, the first gradient as Adam's state holds it, the
  change of the fp32 master weights after ``n`` steps);
* ``save``: ``BlobCheckpointer.save`` of the device state;
* ``gc``: one garbage-collection round over the deployment;
* ``resume``: what ``repro.launch.train.main`` does after a kill: the
  device state dropped and freed, a fresh client and checkpointer on the
  same lineage, the newest checkpoint restored (span ``resume_read``) and
  put on the device with the state's shardings (span ``resume_h2d``), the
  digest cache loaded, the reader rebuilt at the saved cursor, and one
  train step whose loss is read back: a job has resumed when its first
  step returns.  That loss is compared with the loss the same step gave
  without the interruption.

After each GC round the harness reads the newest save back at its version
(``bench.check.save_sample``) before the next steps change the state.  That
check is the harness's own work: its span is ``save_check`` and its time is
left out of the window.
"""

from __future__ import annotations

import time
from typing import Dict, List, Optional, Tuple

import jax
import jax.numpy as jnp

from bench import check, gen
from bench.trace import Spans

# the host spans the operations and the checks write, by name
SPAN_NAMES = ("train_step", "ckpt_save", "gc_round", "save_check", "resume", "resume_read",
              "resume_h2d")


@jax.jit
def leaf_norms(tree):
    return jax.tree.map(lambda x: jnp.sqrt(jnp.sum(jnp.square(x.astype(jnp.float32)))), tree)


def change_norms(master, key, params_abs):
    """Per leaf, the norm of the fp32 master weights' change since the seed's init.

    The key is an argument of the jitted program, never a constant in it, so
    that one compiled program serves every seed."""
    dtypes = {p: s.dtype for p, s in gen.leaves_with_paths(params_abs)}

    def norms(tree, k):
        def one(path, m):
            p = gen.path_str(path)
            start = gen.init_leaf(k, p, m.shape, dtypes[p]).astype(jnp.float32)
            return jnp.sqrt(jnp.sum(jnp.square(m - start)))
        return jax.tree_util.tree_map_with_path(one, tree)
    return jax.jit(norms)(master, key)


def flat_floats(tree) -> Dict[str, float]:
    return {p: float(v) for p, v in gen.leaves_with_paths(tree)}


class Job:
    def __init__(self, system, cfg: dict, traffic: dict, seed: int, spans: Spans):
        self.sys, self.cfg, self.traffic, self.seed = system, cfg, traffic, seed
        self.spans = spans
        self.first: Dict[str, object] = {}
        self.save_differ: List[int] = []   # per save read back after a GC round
        self.timeline: List[tuple] = []    # (op, seconds) of every operation run
        self.newest: Optional[Tuple[object, dict]] = None   # (stats, reader) of the last save
        self.step_no = 0                   # the state's step, kept on the host
        self.step_losses: Dict[int, float] = {}   # each step's first loss, by step number
        self.resume_gaps: List[float] = []  # per resume, its first loss against the unbroken run
        self.reset()

    def reset(self) -> None:
        """Counters of the window (set-up's work does not count)."""
        self.steps_done = 0
        self.tokens = 0
        self.saves: List[object] = []
        self.gc_rounds = 0
        self.resumes = 0
        self.failed = 0
        self.check_s = 0.0

    # ------------------------------------------------------------------ ops
    def run(self, ops: List[dict]) -> None:
        for op in ops:
            t0 = time.perf_counter()
            getattr(self, "op_" + op["op"])(**{k: v for k, v in op.items() if k != "op"})
            self.timeline.append((op["op"], time.perf_counter() - t0))
            if op["op"] == "gc" and self.newest is not None:
                self._check_newest_save()

    def _check_newest_save(self) -> None:
        t0 = time.perf_counter()
        with self.spans.span("save_check"):
            self.save_differ.append(check.save_sample(self, self.seed))
        self.check_s += time.perf_counter() - t0

    def _step(self) -> float:
        s = self.sys
        with self.spans.span("train_step"):
            tokens, labels = s.reader.next_batch()
            batch = {"tokens": jnp.asarray(tokens), "labels": jnp.asarray(labels)}
            s.state, metrics = s.step_fn(s.state, batch)
            loss = float(metrics["loss"])  # waits for the step
        self.steps_done += 1
        self.tokens += tokens.size
        self.step_no += 1
        self.step_losses.setdefault(self.step_no, loss)
        return loss

    def op_train_steps(self, n: int) -> None:
        for _ in range(n):
            self._step()

    def op_first_steps(self, n: int) -> None:
        s = self.sys
        b1 = self.cfg["optimizer"]["b1"]
        losses = [self._step()]
        mu = flat_floats(leaf_norms(s.state["opt"]["mu"]))
        self.first["grad1"] = {p: v / (1 - b1) for p, v in mu.items()}
        losses += [self._step() for _ in range(n - 1)]
        self.first["losses"] = losses
        self.first["change"] = flat_floats(change_norms(
            s.state["opt"]["master"], gen.prng_key(self.seed), s.abstract["params"]))

    def op_save(self) -> None:
        s = self.sys
        step = int(s.state["step"])
        reader = s.reader.state_dict()
        with self.spans.span("ckpt_save"):
            stats = s.ckpt.save(s.state, step=step, extra={"reader": reader})
        self.saves.append(stats)
        self.newest = (stats, reader)

    def op_gc(self) -> None:
        with self.spans.span("gc_round"):
            self.sys.gc_round()
        self.gc_rounds += 1

    def op_resume(self) -> None:
        with self.spans.span("resume"):
            self.first_step_after(self.put_on_device(*self.read_back()))
        self.resumes += 1

    def read_back(self) -> Tuple[object, dict]:
        """A resume's first half: the device state dropped and freed, a fresh
        client and checkpointer, the newest checkpoint read to the host.
        Returns the state as numpy leaves, and the manifest."""
        s = self.sys
        leaves = jax.tree.leaves(s.state)
        s.state = None
        for leaf in leaves:   # frees the device buffers now: two states do not fit
            leaf.delete()
        s.reopen()
        with self.spans.span("resume_read"):
            return s.ckpt.restore(s.abstract, with_manifest=True)

    def put_on_device(self, restored, manifest: dict) -> dict:
        """A resume's second half, up to its first step: the state put on the
        device with its shardings, the digest cache loaded, the reader
        rebuilt at the saved cursor.  Returns the manifest."""
        s = self.sys
        with self.spans.span("resume_h2d"):
            s.state = jax.block_until_ready(jax.device_put(restored, s.shardings))
        s.ckpt.load_digest_cache()
        s.reader = s.reader_at(manifest["extra"].get("reader"))
        self.step_no = manifest["step"]
        return manifest

    def first_step_after(self, manifest: dict) -> None:
        """The resumed job's first step, its loss against the same step's
        without the interruption (none recorded: an infinite gap)."""
        want = self.step_losses.get(manifest["step"] + 1)
        loss = self._step()
        self.resume_gaps.append(abs(loss - want) / abs(want) if want is not None else float("inf"))

    # --------------------------------------------------------------- window
    def _elapsed(self, t0: float, c0: float) -> float:
        """Seconds since ``t0``, less the harness's checks since then."""
        return time.perf_counter() - t0 - (self.check_s - c0)

    def window(self, seconds: float) -> float:
        """Whole cycles until ``seconds`` have passed; returns the elapsed time."""
        t0, c0 = time.perf_counter(), self.check_s
        while self._elapsed(t0, c0) < seconds:
            self.run(self.traffic["cycle"])
        return self._elapsed(t0, c0)

    def cycles(self, n: int) -> float:
        t0, c0 = time.perf_counter(), self.check_s
        for _ in range(n):
            self.run(self.traffic["cycle"])
        return self._elapsed(t0, c0)

    def attempted(self) -> int:
        return self.steps_done + len(self.saves) + self.gc_rounds + self.resumes
