"""TelemetryLogger: the one structured JSONL stream both trainers thread.

A logger is a cheap host-side object: it stamps records (``seq``/``ts``/
``kind``), keeps them in memory (``records``), and — when given a path —
appends each as one JSON line (flushed per record, so a crashed run keeps
everything up to its last round). Phase wall-clock rides a context
manager::

    log = TelemetryLogger("run.jsonl", run="demo")
    with log.phase("local+gossip"):
        params, losses = trainer.step(params, batches, lr)
    log.round(rnd, loss=float(losses.mean()), metrics=summary)

``round`` folds the phase seconds accumulated since the previous round
record into the emitted record (``{"phases": {name: seconds}}``) — the
local-step vs gossip vs host breakdown is whatever phases the caller
brackets. Every phase is also a :func:`span`, so the same names show up on
a profiler timeline when one is being captured.

:func:`span` is the program's one host-span helper: a
``jax.profiler.TraceAnnotation`` (or, given ``step``, a
``StepTraceAnnotation``), timed on the profiler's clock beside the device
operations and close to free while no profiler runs. The trainers mark each
round ``dfl.round`` (its step number is the round index) and its host work
``dfl.batch`` / ``dfl.operands`` / ``dfl.dispatch`` / ``dfl.sync`` /
``dfl.record``; ``SimTrainer.run`` builds the next round's batch between
``dfl.dispatch`` and ``dfl.sync`` under ``sim.batch_ahead``.

``round_every=k`` samples the round records: only every k-th round is
emitted (``rnd % k == 0``), and both trainers consult ``wants_round``
before materializing the record's floats — on off-rounds the per-round
device->host sync is skipped entirely, so a streamed run at ``k > 1``
keeps near the un-streamed throughput. The default ``k=1`` emits every
round and produces a byte-identical stream to pre-knob loggers.
"""
from __future__ import annotations

import contextlib
import json
import time
from typing import Any, IO

import jax

from repro.telemetry.events import validate_event

__all__ = ["TelemetryLogger", "read_jsonl", "span"]


def span(name: str, step: int | None = None):
    """A named host span on the profiler's timeline; with ``step``, a step
    span whose ``step_num`` is ``step`` (a round index)."""
    if step is None:
        return jax.profiler.TraceAnnotation(name)
    return jax.profiler.StepTraceAnnotation(name, step_num=step)


def read_jsonl(path: str) -> list[dict]:
    """Load a telemetry stream back, validating the reserved fields."""
    records = []
    with open(path) as f:
        for line in f:
            line = line.strip()
            if line:
                records.append(validate_event(json.loads(line)))
    return records


class TelemetryLogger:
    """Structured JSONL event stream (see :mod:`repro.telemetry.events`
    for the record schema). ``path=None`` keeps the stream in memory only
    (tests, throwaway runs)."""

    def __init__(self, path: str | None = None, run: str | None = None,
                 round_every: int = 1, **header: Any):
        if round_every < 1:
            raise ValueError(f"round_every must be >= 1, got {round_every}")
        self.path = path
        self.round_every = round_every
        self.records: list[dict] = []
        self._seq = 0
        self._t0 = time.time()
        self._phases: dict[str, float] = {}
        self._fh: IO[str] | None = open(path, "a") if path else None
        if run is not None or header:
            self.event("run", run=run, **header)

    # ------------------------------------------------------------- stream
    def event(self, kind: str, **fields: Any) -> dict:
        record = {"seq": self._seq, "ts": round(time.time() - self._t0, 6),
                  "kind": kind, **fields}
        validate_event(record)
        self._seq += 1
        self.records.append(record)
        if self._fh is not None:
            self._fh.write(json.dumps(record) + "\n")
            self._fh.flush()
        return record

    def wants_round(self, rnd: int) -> bool:
        """True when round ``rnd`` would be emitted under ``round_every``
        sampling. Callers should peek this BEFORE materializing round
        fields: the loss/metrics floats are device->host syncs, and the
        whole point of sampling is to skip that sync on off-rounds."""
        return rnd % self.round_every == 0

    def round(self, rnd: int, **fields: Any) -> dict:
        """One training-round record; folds in (and clears) the phase
        seconds accumulated since the last round record. Off-sample rounds
        (``round_every > 1``) emit nothing and keep accumulating phase
        seconds into the next emitted record."""
        if not self.wants_round(rnd):
            return {}
        phases = {k: round(v, 6) for k, v in self._phases.items()}
        self._phases.clear()
        extra = {"phases": phases} if phases else {}
        return self.event("round", round=rnd, **extra, **fields)

    def repair(self, record: dict) -> dict:
        """An elastic-runtime repair record (splice or permanent mask)."""
        return self.event("repair", **record)

    # ------------------------------------------------------------- phases
    @contextlib.contextmanager
    def phase(self, name: str):
        """Accumulate wall-clock for ``name`` until the next :meth:`round`;
        the block is also a :func:`span` of that name."""
        t0 = time.perf_counter()
        with span(name):
            yield
        self._phases[name] = (self._phases.get(name, 0.0)
                              + time.perf_counter() - t0)

    # -------------------------------------------------------------- query
    def of_kind(self, kind: str) -> list[dict]:
        return [r for r in self.records if r["kind"] == kind]

    def close(self) -> None:
        if self._fh is not None:
            self._fh.close()
            self._fh = None

    def __enter__(self) -> "TelemetryLogger":
        return self

    def __exit__(self, *exc) -> None:
        self.close()
