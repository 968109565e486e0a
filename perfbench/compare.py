"""The comparison that decides ``correct``.

Every request due in the window is compared with the plain reference
(``references/<name>.py``) run on its own spike train, after the window
has closed and the system is freed.  The numbers compared, each with its
limit from the configuration's ``limits``:

- ``missing``: requests due in the window that never came back served
  (failed, refused, or not back a minute after the window closed);
- ``layer0_events_gap``: the largest gap between a result's layer-0 event
  count and its input's spike count (admission encode);
- ``hidden_gap_per_1k``: the hidden layer's spike totals' absolute gaps
  from the reference, summed over the compared requests, per thousand
  requests (the fused kernel's first layer);
- ``output_gap``: the largest gap of an output neuron's spike count;
- ``prediction_mismatch_per_1k``: predictions that differ from the
  reference's, per thousand requests, leaving out the requests whose
  answer rounding could flip: the reference's top two classes tied in
  spike count with membrane sums within ``TIE_MARGIN`` of the largest
  sum (a count tie is broken by the membrane sums, and on some seeds the
  output neurons hardly fire, so most counts tie at zero);
- ``membrane_rel_gap_median``: per request, the largest gap of an output
  neuron's membrane summed over the window (the decision variable that
  breaks the counts' ties, read as a model's logits are), over the
  largest such sum of the reference (at least 1); the median over the
  compared requests.  Spike counts move only where a membrane lies
  within rounding of its threshold, which float32 summed in any order
  and three-pass bfloat16 reach about equally rarely; the membrane moves
  in every request by the rounding itself.  The gap is relative because
  rounding scales with the sums, whose size the seed's weights set.
"""

from __future__ import annotations

import numpy as np

NUMBERS = ("missing", "layer0_events_gap", "hidden_gap_per_1k",
           "output_gap", "prediction_mismatch_per_1k", "membrane_rel_gap_median")
TIE_MARGIN = 1e-4  # relative; sound membrane gaps read about 1e-7


def reference_outputs(ref_mod, params, source, indices, precision: str,
                      block: int):
    """The reference's (hidden totals, output counts, output membrane
    sums, predictions) for the requests ``indices``, in blocks of
    ``block`` trains."""
    import jax
    import jax.numpy as jnp

    hid, out, mem, pred = [], [], [], []
    idx = list(indices)
    for i in range(0, len(idx), block):
        part = idx[i:i + block]
        trains = np.stack([source.train_u8(j) for j in part], axis=1)
        if len(part) < block:  # one compiled shape for every block
            pad = np.zeros((trains.shape[0], block - len(part),
                            trains.shape[2]), trains.dtype)
            trains = np.concatenate([trains, pad], axis=1)
        x = jnp.asarray(jax.device_put(trains), jnp.float32)
        h, o, m, p = ref_mod.forward(params, x, precision=precision)
        n = len(part)
        hid.append(np.asarray(h)[:n])
        out.append(np.asarray(o)[:n])
        mem.append(np.asarray(m)[:n])
        pred.append(np.asarray(p)[:n])
    if not idx:
        return (np.zeros(0), np.zeros((0, 0)), np.zeros((0, 0)),
                np.zeros(0, int))
    return (np.concatenate(hid), np.concatenate(out), np.concatenate(mem),
            np.concatenate(pred))


def _decided(counts, mem, scale: float) -> bool:
    """Whether the reference's answer stands clear of rounding: its top
    two classes differ in spike count or in membrane sum."""
    if counts.size < 2:
        return True
    a, b = np.argsort(counts + 1e-6 * mem)[::-1][:2]
    return bool(counts[a] != counts[b]
                or abs(mem[a] - mem[b]) > TIE_MARGIN * scale)


def numbers(results: dict, due: list, source, ref) -> dict:
    """``results``: request index -> served result (or absent);
    ``due``: the indices due in the window; ``ref``: the reference's
    outputs for ``due``, in that order."""
    ref_hid, ref_out, ref_mem, ref_pred = ref
    served = [(k, j) for k, j in enumerate(due)
              if j in results and results[j].ok]
    n = len(served)
    ev_gap = hid_gap = out_gap = mism = 0.0
    mem_gaps = []
    for k, j in served:
        r = results[j]
        ev = np.asarray(r.events_per_layer, np.float64)
        ev_gap = max(ev_gap, abs(ev[0] - float(source.train_u8(j).sum())))
        hid_gap += abs(ev[1] - float(ref_hid[k]))
        out_gap = max(out_gap, float(
            np.abs(np.asarray(r.spike_counts) - ref_out[k]).max()))
        scale = max(float(np.abs(ref_mem[k]).max()), 1.0)
        if _decided(ref_out[k], ref_mem[k], scale):
            mism += int(r.prediction != int(ref_pred[k]))
        gap = np.abs(np.asarray(r.membrane_sum, np.float64) - ref_mem[k])
        mem_gaps.append(float(gap.max()) / scale
                        if np.all(np.isfinite(gap)) else np.inf)
    per_1k = 1000.0 / max(n, 1)
    return {
        "missing": float(len(due) - n),
        "layer0_events_gap": float(ev_gap),
        "hidden_gap_per_1k": float(hid_gap * per_1k),
        "output_gap": out_gap,
        "prediction_mismatch_per_1k": float(mism * per_1k),
        "membrane_rel_gap_median": (float(np.median(mem_gaps))
                                    if mem_gaps else np.inf),
    }


def verdict(nums: dict, limits: dict) -> bool:
    return all(nums[k] <= float(limits[k]) for k in NUMBERS)


def lines(nums: dict, limits: dict) -> list:
    return [f"compared {k}: {nums[k]!r} (limit {limits[k]!r})"
            for k in NUMBERS]
