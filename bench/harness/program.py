"""Reductions of what the program records about itself: the device time
of ops under a ``jax.named_scope`` of the ADMM round, device idle time
under the program's host spans, its line-search counters and its set-up
spans.  They read keys of the metric readers' context that a plain
``trace.load`` does not give:

* ``ctx["trace"]["devices"][dev]["scoped"]``: ``[scope_path, start_ns,
  dur_ns]`` per op (``harness.xspace.load``);
* ``ctx["counters"]``: ``{"before": probes, "after": probes, "rounds":
  n}``, the trainer's ``state.probes`` ((n_shards, 2): line-search
  evaluations and capped searches) read on the host around ``n`` rounds;
* ``ctx["spans"]``: the set-up's recorded spans, ``[name, parent,
  start_ns, end_ns]`` each (``repro.util.spans.recording``).

Each returns None when its key is missing or holds nothing to read.
"""
from __future__ import annotations

import numpy as np

from harness import trace

SYNC_SPANS = ("train.sync", "train.wait")


def in_scope(path: str, scope: str) -> bool:
    return scope in path.split("/")


def scope_ms_per_round(ctx, scope: str) -> float | None:
    """Device time per round of the ops traced under ``scope`` (a ``while``
    and the ops of its body counted once), the mean over devices."""
    tr = ctx["trace"]
    devs = tr["devices"]
    if ctx["rounds"] <= 0 or not devs or \
            not all("scoped" in d for d in devs.values()):
        return None
    if not any(in_scope(e[0], scope) for d in devs.values()
               for e in d["scoped"]):
        return None
    t0, t1 = trace.window_ns(tr)

    def dev_s(dev):
        evs = [e for e in devs[dev]["scoped"] if in_scope(e[0], scope)]
        return trace.total(trace.merge(trace.clip(evs, t0, t1))) / 1e9
    return 1e3 * trace.mean_over_devices(tr, dev_s) / ctx["rounds"]


def idle_under_spans_ms_per_round(ctx, names=SYNC_SPANS) -> float | None:
    """Device idle time per round that lies under the program's host spans
    ``names``, the mean over devices."""
    tr = ctx["trace"]
    t0, t1 = trace.window_ns(tr)
    host = trace.merge(trace.clip([h for h in tr["host"] if h[0] in names],
                                  t0, t1))
    if ctx["rounds"] <= 0 or not tr["devices"] or not host:
        return None

    def dev_s(dev):
        idle = trace.subtract([(t0, t1)], trace.busy(tr, dev))
        outside = trace.subtract(idle, host)
        return (trace.total(idle) - trace.total(outside)) / 1e9
    return 1e3 * trace.mean_over_devices(tr, dev_s) / ctx["rounds"]


def counter_per_round(ctx, column: int) -> float | None:
    """Column ``column`` of the probes counter, its increase per round,
    the mean over shards."""
    c = ctx.get("counters")
    if not c or c["rounds"] <= 0:
        return None
    diff = np.asarray(c["after"], float) - np.asarray(c["before"], float)
    return float(diff[:, column].mean()) / c["rounds"]


def span_s(ctx, name: str) -> float | None:
    """Seconds in the recorded set-up spans called ``name``."""
    found = [s for s in ctx.get("spans") or () if s[0] == name]
    if not found:
        return None
    return sum(s[3] - s[2] for s in found) / 1e9
