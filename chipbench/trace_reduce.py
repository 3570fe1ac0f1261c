"""Profiler trace -> device busy and idle time, top operations, idle gaps.

Reads the ``.xplane.pb`` that ``jax.profiler`` writes, with nothing but
JAX's own reader (``jax.profiler.ProfileData``).

  * The window is the host event named ``window`` (the harness opens a
    ``jax.profiler.TraceAnnotation`` of that name around its measured
    window); without one it is the span of all device operations.
  * Busy time of a device is the union of its operation intervals on the
    ``XLA Ops`` line (``XLA Modules`` where a trace has no op line),
    clipped to the window; ``busy_s`` is its mean over the devices.
  * ``device_ops``: operations by total time in the window.
  * ``idle_gaps``: the longest stretches in which the first device ran
    nothing, each named by the innermost harness span (a host event whose
    name is in ``span_names``) open at its middle, or ``"no span"``.
"""
from __future__ import annotations

import re
from dataclasses import dataclass, field
from pathlib import Path

import numpy as np

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OP_LINES = ("XLA Ops", "XLA Modules")
TOP = 10


@dataclass
class TraceSummary:
    window_s: float
    busy_s: float | None  # None: the trace holds no device plane
    n_devices: int
    device_ops: list = field(default_factory=list)  # [[name, seconds]]
    idle_gaps: list = field(default_factory=list)  # [[span name, seconds]]


def find_xplane(logdir) -> Path:
    found = sorted(Path(logdir).rglob("*.xplane.pb"))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def _events(line):
    for e in line.events:
        yield e.name, float(e.start_ns), float(e.end_ns)


def _union(iv: np.ndarray) -> np.ndarray:
    """Sorted, merged [start, end] intervals."""
    if iv.size == 0:
        return iv.reshape(0, 2)
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out)


def op_name(name: str) -> str:
    """``%while.119 = (s32[...]...) while(...)`` -> ``while.119 (s32[...]``."""
    head, _, rest = name.partition(" = ")
    short = head.lstrip("%")
    if rest:
        short += " " + rest[:48]
    return short[:96]


def reduce(path, window: str = "chipbench.window",
           span_names=()) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(str(path))
    devices, spans, win = [], [], None
    names = set(span_names)
    for plane in pd.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {ln.name: ln for ln in plane.lines}
            line = next((lines[n] for n in OP_LINES if n in lines), None)
            devices.append([] if line is None else list(_events(line)))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for n, s, e in _events(line):
                    if n == window and (win is None or s < win[0]):
                        win = (s, e)
                    elif n in names:
                        spans.append((s, e, n))
    if win is None:
        ends = [(s, e) for d in devices for _, s, e in d]
        win = (min(s for s, _ in ends), max(e for _, e in ends)) if ends \
            else (0.0, 0.0)
    w0, w1 = win
    window_s = (w1 - w0) * 1e-9
    if not devices:
        return TraceSummary(window_s=window_s, busy_s=None, n_devices=0)

    busy, per_op = [], {}
    unions = []
    for evs in devices:
        iv = np.asarray([(max(s, w0), min(e, w1)) for _, s, e in evs
                         if e > w0 and s < w1], float).reshape(-1, 2)
        u = _union(iv)
        unions.append(u)
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9 if u.size else 0.0)
        for n, s, e in evs:
            if e > w0 and s < w1:
                k = op_name(n)
                per_op[k] = per_op.get(k, 0.0) + (min(e, w1) - max(s, w0)) * 1e-9
    ops = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP]

    u = unions[0]
    edges = np.concatenate([[w0], u.reshape(-1), [w1]]).reshape(-1, 2)
    gaps = [(s, e) for s, e in edges if e > s]
    gaps.sort(key=lambda g: g[0] - g[1])
    named = []
    for s, e in gaps[:TOP]:
        mid = (s + e) / 2
        open_ = [sp for sp in spans if sp[0] <= mid <= sp[1]]
        name = max(open_, key=lambda sp: sp[0])[2] if open_ else "no span"
        named.append([name, float(e - s) * 1e-9])
    return TraceSummary(window_s=window_s, busy_s=float(np.mean(busy)),
                        n_devices=len(devices),
                        device_ops=[[k, v] for k, v in ops],
                        idle_gaps=named)
