"""Barrier-free durability: donated-pipeline-safe async device snapshots.

The scan executor's fast paths (``metrics="tap"|"none"``) never
materialise the mid-run state on host — that is exactly why they are
fast, and exactly why they had no durability.  :class:`AsyncSnapshotter`
closes the gap without reintroducing barriers:

1. ``offer(round, state)`` dispatches a cached NON-donating jitted
   device copy of the carry.  The copy is enqueued on the device stream
   *before* the next chunk launch donates the carry's buffers, and
   devices execute in dispatch order, so the snapshot reads consistent
   data no matter how far ahead the host races.
2. Every leaf of the copy starts a ``copy_to_host_async`` transfer and
   the pair is parked in a two-deep pending queue (double buffer).
3. Offering the NEXT snapshot finalises the previous one: by then its
   transfer has had a whole snapshot cadence to complete, so the numpy
   materialisation inside :func:`repro.checkpoint.save` is (near) free,
   and the write itself is the ordinary ATOMIC checkpoint save.

The device pipeline therefore never drains mid-run: the host only ever
waits for data the device finished a cadence ago.  A SIGKILL at any
point loses at most the two pending snapshots; everything older is an
atomically-written, sha-verified checkpoint directory that
:meth:`AsyncSnapshotter.latest` will find and
:func:`repro.checkpoint.restore` will load — and because snapshots land
on chunk boundaries and the plan's data keys are pure functions of
(seed, round), a resumed run is bit-for-bit the uninterrupted one.
"""
from __future__ import annotations

import os
import re
import shutil
from collections import deque
from typing import Optional

from ..obs import span
from . import checkpointer

_ROUND_DIR = re.compile(r"^round-(\d{8})$")


class AsyncSnapshotter:
    """Periodic async snapshots of a scan run's carried state.

    ``every`` is the cadence knob in ROUNDS: a chunk boundary ``hi`` is
    due when ``hi % every == 0`` (plus the final boundary).  Boundaries
    are the only offer points, so pick ``every`` as a multiple of
    ``rounds_per_launch`` to get exactly the cadence you asked for —
    other values snapshot at the boundaries the modulo happens to hit.

    ``keep`` bounds disk: only the newest ``keep`` snapshot directories
    survive pruning (the crash-recovery window).
    """

    def __init__(self, path: str, every: int, *, keep: int = 2,
                 meta: Optional[dict] = None, recorder=None):
        if every < 1:
            raise ValueError(f"snapshot cadence must be >= 1 (got {every})")
        if keep < 1:
            raise ValueError(f"keep must be >= 1 (got {keep})")
        self.path = str(path)
        self.every = int(every)
        self.keep = int(keep)
        self._meta = dict(meta or {})
        self.recorder = recorder            # repro.obs.Recorder | None
        self._copy_jit = None
        self._pending: deque = deque()      # (round, on-device copy)
        self._written: list = []            # (round, dirname), ascending

    # ------------------------------------------------------------- schedule
    def due(self, round_i: int, total_rounds: int) -> bool:
        """Is the chunk boundary ``round_i`` a snapshot point?"""
        return round_i % self.every == 0 or round_i >= total_rounds

    # --------------------------------------------------------------- offers
    def offer(self, round_i: int, state, meta: Optional[dict] = None) -> None:
        """Snapshot the carry at round ``round_i`` without blocking on it.

        Dispatches the device copy + async host fetch and returns; the
        PREVIOUS pending snapshot (whose fetch has been in flight since
        the last offer) is finalised to disk on the way out, keeping at
        most one snapshot in flight (the double buffer).  ``meta`` is
        per-offer metadata merged into the saved ``meta.json`` — the slot
        server rides its host-side ledger (queue, rid→slot map, emitted
        tokens, retry/backoff state) here so a crash-resume restores the
        DRIVER, not just the device carry."""
        import jax

        if self._copy_jit is None:
            import jax.numpy as jnp

            # non-donating identity copy: output buffers are fresh (no
            # donation means XLA cannot alias them to the inputs), so the
            # next chunk donating the carry cannot clobber the snapshot
            self._copy_jit = jax.jit(
                lambda s: jax.tree_util.tree_map(jnp.copy, s))
        with span(self.recorder, "snapshot_copy", "snapshot",
                  round=int(round_i)):
            snap = self._copy_jit(state)
        for leaf in jax.tree_util.tree_leaves(snap):
            if hasattr(leaf, "copy_to_host_async"):
                leaf.copy_to_host_async()
        self._pending.append((int(round_i), snap, dict(meta or {})))
        while len(self._pending) > 1:
            self._write_oldest()

    def drain(self) -> Optional[int]:
        """Flush every pending snapshot to disk (end of run); returns the
        newest written round, or None when nothing was ever offered."""
        while self._pending:
            self._write_oldest()
        return self._written[-1][0] if self._written else None

    # ---------------------------------------------------------------- disk
    def round_dir(self, round_i: int) -> str:
        return os.path.join(self.path, f"round-{round_i:08d}")

    def _write_oldest(self) -> None:
        r, snap, extra = self._pending.popleft()
        rec = self.recorder
        meta = {**self._meta, **extra, "round": r, "kind": "snapshot"}
        # in the trace this span sits a whole cadence AFTER the
        # snapshot_offer/snapshot_copy of the same round — the visible
        # proof the two-deep async window overlaps compute
        with span(rec, "snapshot_finalise", "snapshot", round=r):
            checkpointer.save(self.round_dir(r), snap, step=r, meta=meta)
        if rec is not None:
            rec.count("snapshot_writes")
        self._written.append((r, self.round_dir(r)))
        self._prune()

    def _prune(self) -> None:
        while len(self._written) > self.keep:
            _, old = self._written.pop(0)
            shutil.rmtree(old, ignore_errors=True)

    @staticmethod
    def latest(path: str) -> Optional[tuple]:
        """Newest RESTORABLE snapshot under ``path`` as ``(round,
        dirname)``, or None.  Directories that fail the checkpoint
        integrity check (e.g. a save torn by the crash being recovered
        from) are skipped — that is the whole point of keeping more than
        one."""
        if not os.path.isdir(path):
            return None
        rounds = []
        for name in os.listdir(path):
            m = _ROUND_DIR.match(name)
            if m:
                rounds.append((int(m.group(1)), os.path.join(path, name)))
        for r, dirname in sorted(rounds, reverse=True):
            try:
                checkpointer.verify(dirname)
            except checkpointer.CheckpointError:
                continue
            return r, dirname
        return None
