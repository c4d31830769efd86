"""From a profiler trace to device busy time, idle gaps and host spans.

The JAX profiler writes ``<dir>/plugins/profile/<time>/*.xplane.pb``;
``jax.profiler.ProfileData`` reads it.  A device is a plane named
``/device:TPU:<n>``; its operations are the events of its ``XLA Ops``
line, where a ``while`` or ``call`` spans the operations it runs, and its
programs the events of its ``XLA Modules`` line.  The benchmark's own host
spans are the ``bench:*`` events of the host plane.  The traced window is
the ``bench:window`` span.

Host and device events share one clock only roughly: on a v5e the device's
lay 1-2 ms early against the host spans around them (the recorded trace
beside the tests shows it).  So a number that sets device time against a
host span is read over spans of seconds, such as whole batches.
"""

from __future__ import annotations

import dataclasses
import glob
import os
import re

import numpy as np

_LAYOUT = re.compile(r"\{[^{}]*\}")

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench:"


@dataclasses.dataclass
class Trace:
    window: tuple                 # (start, end) ns of bench:window
    busy: list                    # per device: (m, 2) merged intervals, ns
    ops: dict                     # device op name -> self ns, all devices
    spans: list                   # (name, start, end) ns host spans

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self, a: float | None = None, b: float | None = None) -> float:
        """Seconds some operation ran on the device within [a, b] (ns;
        default the window), averaged over the devices."""
        a = self.window[0] if a is None else a
        b = self.window[1] if b is None else b
        return float(np.mean([_covered(iv, a, b) for iv in self.busy])) * 1e-9

    def spans_named(self, name: str) -> list:
        return [(s, e) for n, s, e in self.spans if n == SPAN_PREFIX + name]

    def top_ops(self, top: int = 10) -> list:
        items = sorted(self.ops.items(), key=lambda kv: -kv[1])[:top]
        return [[name, ns * 1e-9] for name, ns in items]

    def idle_gaps(self, top: int = 10) -> list:
        """The longest gaps in the first device's busy time within the
        window, each named by the innermost host span around its middle."""
        a, b = self.window
        iv = _clip(self.busy[0], a, b)
        edges = np.concatenate([[a], iv.reshape(-1), [b]]).reshape(-1, 2)
        gaps = [(s, e) for s, e in edges if e > s]
        gaps.sort(key=lambda g: g[0] - g[1])
        out = []
        for s, e in gaps[:top]:
            mid = 0.5 * (s + e)
            around = [(se - ss, n) for n, ss, se in self.spans
                      if ss <= mid <= se and n != SPAN_PREFIX + "window"]
            name = min(around)[1][len(SPAN_PREFIX):] if around else "window"
            out.append([name, float(e - s) * 1e-9])
        return out


def _merge(iv: np.ndarray) -> np.ndarray:
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0])]
    out = [list(iv[0])]
    for s, e in iv[1:]:
        if s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return np.asarray(out, np.float64)


def _clip(iv: np.ndarray, a: float, b: float) -> np.ndarray:
    iv = np.clip(iv, a, b)
    return iv[iv[:, 1] > iv[:, 0]]


def _covered(iv: np.ndarray, a: float, b: float) -> float:
    c = _clip(iv, a, b)
    return float((c[:, 1] - c[:, 0]).sum())


def _op_name(text: str, module: str) -> str:
    """``%fusion.6 = f32[8,32]{1,0:T(8,128)} fusion(...)`` in module
    ``jit_f(123)`` -> ``jit_f/fusion.6 fusion f32[8,32]``."""
    name, _, rest = text.partition(" = ")
    rest = _LAYOUT.sub("", rest)
    if rest.startswith("("):
        kind = rest[_closing(rest) + 1:].split("(")[0].strip()
        shape = "(...)"
    else:
        shape, _, tail = rest.partition(" ")
        kind = tail.split("(")[0]
    return f"{module.split('(')[0]}/{name.lstrip('%')} {kind} {shape}"


def _closing(text: str) -> int:
    depth = 0
    for i, ch in enumerate(text):
        depth += {"(": 1, ")": -1}.get(ch, 0)
        if depth == 0:
            return i
    return len(text) - 1


def _self_times(events: list) -> list:
    """(start, end, text) events, nested as on the ``XLA Ops`` line ->
    each with its duration less that of the events it directly holds."""
    events = sorted(events, key=lambda e: (e[0], -e[1]))
    own = [e[1] - e[0] for e in events]
    stack = []
    for i, (s, e, _) in enumerate(events):
        while stack and events[stack[-1]][1] <= s:
            stack.pop()
        if stack:
            own[stack[-1]] -= e - s
        stack.append(i)
    return own


def xplane_file(logdir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return found[-1]


def reduce(path: str) -> Trace:
    """Read one ``.xplane.pb`` into a :class:`Trace`."""
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    busy, ops, spans = [], {}, []
    for plane in data.planes:
        if DEVICE_PLANE.match(plane.name):
            lines = {line.name: [(ev.start_ns, ev.start_ns + ev.duration_ns,
                                  ev.name) for ev in line.events]
                     for line in plane.lines}
            events = lines.get(OPS_LINE, [])
            modules = sorted(lines.get(MODULES_LINE, []))
            starts = np.asarray([m[0] for m in modules])
            for (s, e, text), own in zip(sorted(
                    events, key=lambda x: (x[0], x[0] - x[1])),
                    _self_times(events)):
                at = np.searchsorted(starts, s, side="right") - 1
                module = modules[at][2] if at >= 0 and s < modules[at][1] \
                    else "?"
                key = _op_name(text, module)
                ops[key] = ops.get(key, 0.0) + own
            iv = np.asarray([(s, e) for s, e, _ in events], np.float64)
            busy.append(_merge(iv.reshape(-1, 2)))
        elif plane.name == HOST_PLANE:
            for line in plane.lines:
                for ev in line.events:
                    if ev.name.startswith(SPAN_PREFIX):
                        spans.append((ev.name, ev.start_ns,
                                      ev.start_ns + ev.duration_ns))
    if not busy:
        raise ValueError(f"no device plane in {path}")
    windows = [(s, e) for n, s, e in spans if n == SPAN_PREFIX + "window"]
    if len(windows) != 1:
        raise ValueError(f"{len(windows)} bench:window spans in {path}")
    return Trace(window=windows[0], busy=busy, ops=ops, spans=spans)
