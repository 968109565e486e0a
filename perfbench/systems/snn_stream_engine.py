"""The system under test: ``SNNStreamEngine`` as ``launch/serve.py --snn``
builds it.

Backend "auto" (resolved from the platform: the fused chunk kernel on a
TPU), the configuration's slot count and chunk length, no capacity plan
(so the layer-0 event capacity C equals the input width), the serving
CLI's SLO set, no admission policy, fault injector or preemption.
"""

from __future__ import annotations

import dataclasses

import numpy as np

# Names under which the program's work shows in a device trace
PROGRAMS = {
    "admit": "jit_admit_spikes",
    "chunk": "jit__chunk_fn",
    "kernel": "snn_chunk",
}


@dataclasses.dataclass
class Result:
    request_id: int
    ok: bool
    prediction: int
    spike_counts: np.ndarray  # (n_out,)
    events_per_layer: np.ndarray  # (layers,): input events, hidden spikes
    queue_wait_s: float
    membrane_sum: np.ndarray  # (n_out,) output membrane over the window


class System:
    def __init__(self, cfg: dict, params: dict):
        from repro.core import snn
        from repro.obs import default_slos
        from repro.serving.snn_engine import SNNStreamEngine

        eng = cfg["engine"]
        self.snn_cfg = snn.SNNConfig(
            layer_sizes=tuple(cfg["layer_sizes"]),
            num_steps=int(cfg["num_steps"]),
            neuron_kind=cfg["neuron_kind"],
            reset=cfg["reset"],
            refractory_steps=int(cfg["refractory_steps"]),
        )
        self.engine = SNNStreamEngine(
            params, self.snn_cfg,
            num_slots=int(eng["num_slots"]),
            chunk_steps=int(eng["chunk_steps"]),
            seed=1,
            backend=eng["backend"],
            slos=default_slos(p99_target_s=1.0),
        )
        # The output membrane summed over a window is the engine's decision
        # variable beside the spike counts (it breaks their ties), folded
        # from the chunk's stats like them, but not carried on
        # StreamResult; it is read where the engine finalizes a request.
        self._membrane = {}
        finalize = self.engine._finalize

        def finalize_and_keep(s):
            res = finalize(s)
            self._membrane[res.request_id] = np.array(
                self.engine._slot_memsum[s], np.float64)
            return res

        self.engine._finalize = finalize_and_keep
        self.num_slots = self.engine.S
        self.chunk_steps = self.engine.Tc
        self.capacity = self.engine.C
        self.backend = self.engine.backend

    def submit(self, train: np.ndarray) -> int:
        from repro.serving.snn_engine import StreamRequest

        return self.engine.submit(StreamRequest(spikes=train))

    def poll(self) -> list:
        return [
            Result(
                request_id=r.request_id,
                ok=r.disposition == "ok",
                prediction=int(r.prediction),
                spike_counts=np.asarray(r.spike_counts),
                events_per_layer=np.asarray(r.events_per_layer),
                queue_wait_s=float(r.queue_wait_s),
                membrane_sum=self._membrane.pop(
                    r.request_id, np.full(len(r.spike_counts), np.nan)),
            )
            for r in self.engine.poll()
        ]

    def idle(self) -> bool:
        return self.engine.idle()

    def queue_depth(self) -> int:
        return self.engine.queue_depth()

    def compiled_fns(self) -> dict:
        """The jitted functions the window drives, for recompile checks."""
        return {
            "chunk": self.engine._chunk,
            "admit": self.engine._admit_spikes_fn,
        }

    def reset_tick_stats(self) -> None:
        self.engine.reset_tick_stats()

    def tick_stats(self) -> dict:
        """Mean host time per tick: scheduling prep and chunk dispatch
        (an enqueue on a TPU), and the tick count."""
        b = self.engine.tick_breakdown()
        return {
            "ticks": b["ticks"],
            "host_prep_us": b["host_prep_us"],
            "dispatch_us": b["dispatch_us"],
            "stats_fetch_us": b["stats_fetch_us"],
        }

    def health(self) -> dict:
        """Counters a sound run keeps at zero."""
        snap = self.engine.metrics_snapshot()
        return {
            "backend": self.engine.backend,
            "demotions": snap["engine.faults.backend_demoted"]["value"],
            "retries": snap["engine.faults.chunk_retries"]["value"],
            "quarantined": snap["engine.requests.quarantined"]["value"],
            "steady_state_recompiles": self.engine.steady_state_recompiles(),
        }

    def unsound(self, platform: str) -> list:
        """What took the run off the path the configuration states: a
        chunk demoted, retried or quarantined, a steady-state recompile,
        or, on a TPU, any backend but the fused kernel."""
        h = self.health()
        bad = [f"{k} {h[k]:g}" for k in ("demotions", "retries",
                                          "quarantined",
                                          "steady_state_recompiles")
               if h[k]]
        if platform == "tpu" and h["backend"] != "fused":
            bad.append(f"backend {h['backend']!r} on a TPU, not the fused "
                       "kernel")
        return bad

    def close(self) -> None:
        self.engine = None
