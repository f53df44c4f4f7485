"""Test-side oracles: the straightforward forms the optimized paths must
reproduce.

- ``reference_local_training``: one ``small_models.local_train`` call
  per worker, the per-worker loop the vectorized engine replaces.
- ``reference_fused_local_training``: the same loop behind
  ``engine.fused_local_training``'s signature, for ``monkeypatch``.
- ``WaterFillingScheduler``: re-prices an uplink by the full weighted
  water-filling (``congestion.fair_share_rates``) and schedules one
  completion event per flow, where ``EventCore`` keeps an ``UplinkState``
  and one event per uplink.
- ``PerEventScheduler``: one global heap entry per worker cycle event,
  where ``AsyncBufferScheduler`` batches them into one per app cohort.
"""
from __future__ import annotations

import jax

from repro import tracing
from repro.core.congestion import fair_share_rates
from repro.core.sim import AsyncBufferScheduler
from repro.fl import small_models as sm


def reference_local_training(app, workers, params=None):
    """(deltas, weights, losses) per worker, in ``workers`` order."""
    start = app.params if params is None else params
    logits_fn = sm.LOGITS[app.model]
    deltas, weights, losses = [], [], []
    for w in workers:
        x, y = app.data[w]
        new_p, loss = sm.local_train(
            start, start, x, y,
            logits_fn=logits_fn, steps=app.local_steps, lr=app.lr, mu=app.mu,
        )
        deltas.append(jax.tree.map(lambda a, b: a - b, new_p, start))
        weights.append(float(len(y)))
        losses.append(float(tracing.pull(loss)))
    return deltas, weights, losses


def reference_fused_local_training(jobs):
    return [reference_local_training(app, ws, start) for app, ws, start in jobs]


class WaterFillingScheduler(AsyncBufferScheduler):
    """Full water-filling re-price, one completion event per flow."""

    def __init__(self, *args, **kwargs):
        super().__init__(*args, **kwargs)
        self._flow_ev: dict[int, int] = {}

    def _reset_clock(self) -> None:
        super()._reset_clock()
        self._flow_ev.clear()

    def cancel_flow(self, fid: int) -> None:
        ev = self._flow_ev.pop(fid, None)
        if ev is not None:
            self.cancel(ev)
        super().cancel_flow(fid)

    def _reprice_uplink(self, sender: int) -> None:
        fids = self._flows_by_sender.get(sender)
        if not fids:
            return
        flows = [self._flows[fid] for fid in fids]
        for f in flows:
            f.delivered_mbit = min(
                f.total_mbit, f.delivered_mbit + f.rate * (self.now - f.t_last) * 1e-3
            )
            f.t_last = self.now
        # flows in one group split a single weight share and rate cap
        group_n: dict = {}
        for f in flows:
            group_n[f.group] = group_n.get(f.group, 0) + 1
        rates = fair_share_rates(
            float(self._cap_mbps[sender]),
            [f.weight / group_n[f.group] for f in flows],
            [None if f.rate_cap is None else f.rate_cap / group_n[f.group] for f in flows],
        )
        for f, r in zip(flows, rates):
            f.rate = r
            ev = self._flow_ev.get(f.fid)
            if ev is not None:
                self.cancel(ev)
            remaining = f.total_mbit - f.delivered_mbit
            self._flow_ev[f.fid] = self.schedule(
                1e3 * remaining / max(r, 1e-9),
                lambda t, fid=f.fid: self._finish_flow(fid, t),
            )


class PerEventScheduler(AsyncBufferScheduler):
    """Every worker cycle event is its own global heap entry."""

    def _sched_worker(self, ai, delay_ms, callback):
        return self.schedule(delay_ms, callback)
