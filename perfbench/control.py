"""The control and the planted faults of the comparison that decides
``correct``.

``ReferenceSystem`` puts the plain reference in the program's place,
computed in the precision below the one the configuration states
("high": three bfloat16 passes, for float32 at "highest"); the comparison
has to find it not correct.  ``broken`` wraps the real system with one
fault planted in its timed path, each of which the comparison has to
find:

- ``state_unchanged``: the chunk returns the neuron states it was given;
- ``half_batch``: the second half of the slots is left out (their stats
  come back as zeros);
- ``answer_altered``: one output spike is added to slot 0's count where
  the chunk produces it;
- ``prediction_altered``: the class of every request finalized in slot 0
  is moved to the next one where the engine retires it.

Neither the benchmark's runs nor the program use this module: the tool
``tools/readings.py`` and the tests do.
"""

from __future__ import annotations

import collections
import dataclasses

import numpy as np

from perfbench import spec


class ReferenceSystem:
    """Serves each request with the reference at ``precision``: a poll
    computes up to ``BATCH`` queued requests in one call (one shape, so
    one compile, in the warm-up)."""

    BATCH = 32

    def __init__(self, cfg: dict, params: dict, precision: str = "high",
                 num_slots: int = 8):
        self.ref = spec.named("references", cfg["reference"])
        self.result = spec.named("systems", cfg["system"]).Result
        self.params = params
        self.precision = precision
        self.num_slots = num_slots
        self.chunk_steps = int(cfg["engine"]["chunk_steps"])
        self.capacity = int(cfg["layer_sizes"][0])
        self.backend = f"reference@{precision}"
        self._queue = collections.deque()
        self._next = 0

    def submit(self, train: np.ndarray) -> int:
        rid = self._next
        self._next += 1
        self._queue.append((rid, np.asarray(train)))
        return rid

    def poll(self) -> list:
        import jax.numpy as jnp

        batch = [self._queue.popleft()
                 for _ in range(min(len(self._queue), self.BATCH))]
        if not batch:
            return []
        x = np.zeros((batch[0][1].shape[0], self.BATCH,
                      batch[0][1].shape[1]), np.float32)
        for k, (_, t) in enumerate(batch):
            x[:, k] = t
        hid, out, mem, pred = (np.asarray(a) for a in self.ref.forward(
            self.params, jnp.asarray(x), precision=self.precision))
        return [
            self.result(request_id=rid, ok=True, prediction=int(pred[k]),
                   spike_counts=out[k],
                   events_per_layer=np.array([float(t.sum()), hid[k]]),
                   queue_wait_s=0.0, membrane_sum=mem[k])
            for k, (rid, t) in enumerate(batch)
        ]

    def idle(self) -> bool:
        return not self._queue

    def queue_depth(self) -> int:
        return len(self._queue)

    def compiled_fns(self) -> dict:
        return {}

    def reset_tick_stats(self) -> None:
        pass

    def tick_stats(self) -> dict:
        return {"ticks": 0}

    def health(self) -> dict:
        return {"backend": self.backend}

    def unsound(self, platform: str) -> list:
        return []

    def close(self) -> None:
        pass


FAULTS = ("state_unchanged", "half_batch", "answer_altered",
          "prediction_altered")


def broken(fault: str):
    """A ``system_override`` that plants ``fault`` in the engine's
    compiled chunk, or where it finalizes a request."""
    if fault not in FAULTS:
        raise ValueError(f"unknown fault {fault!r}")

    def override(system):
        import jax.numpy as jnp

        eng = system.engine
        if fault == "prediction_altered":
            finalize = eng._finalize

            def altered(s):
                res = finalize(s)
                if s == 0:
                    res = dataclasses.replace(res, prediction=(
                        res.prediction + 1) % len(res.spike_counts))
                return res

            eng._finalize = altered
            return system
        plain = eng._chunk_nodonate

        def chunk(prepared, states, ring, meta):
            new_states, new_meta, stats = plain(prepared, states, ring, meta)
            if fault == "state_unchanged":
                new_states = states
            elif fault == "half_batch":
                keep = (jnp.arange(eng.S) < eng.S // 2)
                stats = {
                    k: (v if k == "fault" else jnp.where(
                        keep.reshape((-1,) + (1,) * (v.ndim - 1)), v, 0))
                    for k, v in stats.items()
                }
            else:
                stats = {**stats,
                         "counts": stats["counts"].at[0, 0].add(1.0)}
            return new_states, new_meta, stats

        eng._chunk = chunk
        return system

    return override
