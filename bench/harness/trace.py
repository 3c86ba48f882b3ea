"""Profiler traces: recording a slice of the window, and reducing the
trace to device intervals.

``load`` turns the profiler's ``.xplane.pb`` into a small plain structure,
the same one the recorded test trace holds:

    {"window": [t0_ns, t1_ns],                 # the harness's host spans
     "host": [[name, start_ns, dur_ns], ...],  # host events in the window
     "devices": {"0": {"ops": [[name, start_ns, dur_ns], ...],
                       "modules": [[name, start_ns, dur_ns], ...]}, ...}}

``ops`` are the events of a device's "XLA Ops" line (one per executed HLO
operation or kernel, named by its HLO instruction, ``community_spmm_ell.7``),
``async`` those of its "Async XLA Ops" line, ``modules`` those of its "XLA
Modules" line (one per executed program, named after the jitted function,
``jit_step(<fingerprint>)``).  Every reduction below works on that
structure alone.
"""
from __future__ import annotations

import glob
import os

# the harness's host span around each chunk of rounds in the window
HARNESS_SPANS = ("train_chunk",)
LINES = {"XLA Ops": "ops", "Async XLA Ops": "async", "XLA Modules": "modules"}


def start(log_dir: str) -> None:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0   # the Python tracer slows the host
    opts.host_tracer_level = 2
    jax.profiler.start_trace(log_dir, profiler_options=opts)


def stop() -> None:
    import jax
    jax.profiler.stop_trace()


def span(name: str):
    """A harness span, written into the profiler's host trace."""
    import jax
    return jax.profiler.TraceAnnotation(name)


def _xplane(log_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                             recursive=True))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return found[-1]


def op_name(event_name: str) -> str:
    """``%community_spmm_ell.7 = f32[...] custom-call(...)`` ->
    ``community_spmm_ell.7``."""
    return event_name.split(" = ", 1)[0].lstrip("%")


def load(log_dir: str) -> dict:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(_xplane(log_dir))
    host, devices, spans = [], {}, []
    for plane in pd.planes:
        name = plane.name
        if name.startswith("/device:") and ":" in name[len("/device:"):]:
            dev = name.rsplit(":", 1)[-1]
            if not dev.isdigit():
                continue
            rec = devices.setdefault(dev, {k: [] for k in LINES.values()})
            for line in plane.lines:
                key = LINES.get(line.name)
                if key is None:
                    continue
                rec[key].extend([op_name(e.name), float(e.start_ns),
                                 float(e.duration_ns)] for e in line.events)
        elif name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    ev = [e.name, float(e.start_ns), float(e.duration_ns)]
                    if e.name in HARNESS_SPANS:
                        spans.append(ev)
                    host.append(ev)
    if not spans:
        raise ValueError("the trace holds none of the harness's spans")
    t0 = min(s[1] for s in spans)
    t1 = max(s[1] + s[2] for s in spans)
    host = [h for h in host if h[1] < t1 and h[1] + h[2] > t0]
    for rec in devices.values():
        for key in LINES.values():
            rec[key] = [e for e in rec[key] if e[1] < t1 and e[1] + e[2] > t0]
    return {"window": [t0, t1], "host": host, "devices": devices}


def save(tr: dict, path: str) -> None:
    """Write a loaded trace as gzipped JSON (the recorded test trace)."""
    import gzip
    import json
    with gzip.open(path, "wt") as fh:
        json.dump(tr, fh)


def read_saved(path: str) -> dict:
    import gzip
    import json
    with gzip.open(path, "rt") as fh:
        return json.load(fh)


# ---------------------------------------------------------------------------
# interval arithmetic
# ---------------------------------------------------------------------------

def clip(events, t0: float, t1: float) -> list[tuple[float, float]]:
    """[start, end) of each event, cut to the window; empty ones dropped."""
    out = []
    for _, s, d in events:
        a, b = max(s, t0), min(s + d, t1)
        if b > a:
            out.append((a, b))
    return out


def merge(intervals) -> list[tuple[float, float]]:
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def total(intervals) -> float:
    return sum(b - a for a, b in intervals)


def subtract(intervals, covered) -> list[tuple[float, float]]:
    """The parts of ``intervals`` (merged) not inside ``covered`` (merged)."""
    out, j = [], 0
    covered = list(covered)
    for a, b in intervals:
        cur = a
        while j < len(covered) and covered[j][1] <= cur:
            j += 1
        k = j
        while k < len(covered) and covered[k][0] < b:
            ca, cb = covered[k]
            if ca > cur:
                out.append((cur, min(ca, b)))
            cur = max(cur, cb)
            if cur >= b:
                break
            k += 1
        if cur < b:
            out.append((cur, b))
    return out


# ---------------------------------------------------------------------------
# reductions
# ---------------------------------------------------------------------------

def window_ns(tr: dict) -> tuple[float, float]:
    return tr["window"][0], tr["window"][1]


def busy(tr: dict, dev: str) -> list[tuple[float, float]]:
    t0, t1 = window_ns(tr)
    return merge(clip(tr["devices"][dev]["ops"], t0, t1))


def busy_s(tr: dict) -> float:
    """Seconds in which an operation ran, averaged over the devices."""
    devs = sorted(tr["devices"])
    if not devs:
        return 0.0
    return sum(total(busy(tr, d)) for d in devs) / len(devs) / 1e9


def window_s(tr: dict) -> float:
    t0, t1 = window_ns(tr)
    return (t1 - t0) / 1e9


def time_in(tr: dict, dev: str, line: str, match) -> float:
    """Seconds of ``line`` events on ``dev`` whose name ``match`` accepts,
    inside the window (overlaps counted once)."""
    t0, t1 = window_ns(tr)
    evs = [e for e in tr["devices"][dev][line] if match(e[0])]
    return total(merge(clip(evs, t0, t1))) / 1e9


def mean_over_devices(tr: dict, fn) -> float:
    devs = sorted(tr["devices"])
    return sum(fn(d) for d in devs) / len(devs)


def exposed_s(tr: dict, dev: str, match) -> float:
    """Seconds of matching ops during which the device runs no other op."""
    t0, t1 = window_ns(tr)
    ops = tr["devices"][dev]["ops"]
    mine = merge(clip([e for e in ops if match(e[0])], t0, t1))
    other = merge(clip([e for e in ops if not match(e[0])], t0, t1))
    return total(subtract(mine, other)) / 1e9


def _host_label(tr: dict, t: float) -> str:
    """The harness span around ``t`` and the innermost host event under
    it, as ``span/event``."""
    around = [h for h in tr["host"] if h[1] <= t < h[1] + h[2]]
    spans = [h for h in around if h[0] in HARNESS_SPANS]
    span_name = min(spans, key=lambda h: h[2])[0] if spans else "none"
    inner = [h for h in around if h[0] not in HARNESS_SPANS]
    if not inner:
        return span_name
    return f"{span_name}/{min(inner, key=lambda h: h[2])[0]}"


def op_kind(name: str) -> str:
    """``community_spmm_ell.7`` -> ``community_spmm_ell``."""
    base, _, suffix = name.rpartition(".")
    return base if base and suffix.isdigit() else name


def breakdown(tr: dict, top: int = 10) -> dict:
    """The kinds of device op that took most time (seconds per device,
    mean over devices; an op nested in a ``while`` counts in both) and the
    longest idle gaps, each labelled by what the host was doing in the
    middle of it."""
    t0, t1 = window_ns(tr)
    devs = sorted(tr["devices"])
    per_op: dict[str, float] = {}
    gaps = []
    for d in devs:
        for name, s, dur in tr["devices"][d]["ops"]:
            a, b = max(s, t0), min(s + dur, t1)
            if b > a:
                kind = op_kind(name)
                per_op[kind] = per_op.get(kind, 0.0) + (b - a) / 1e9
        idle = subtract([(t0, t1)], busy(tr, d))
        gaps.extend((b - a, a, b) for a, b in idle)
    ops = sorted(((k, v / len(devs)) for k, v in per_op.items()),
                 key=lambda kv: -kv[1])[:top]
    gaps = sorted(gaps, reverse=True)[:top]
    return {"device_ops": [[k, v] for k, v in ops],
            "idle_gaps": [[_host_label(tr, (a + b) / 2), g / 1e9]
                          for g, a, b in gaps]}
