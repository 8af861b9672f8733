"""Reduction of a JAX profiler trace (``.xplane.pb``) to the numbers the
per-layer readers use.

Device planes are named ``/device:TPU:<n>``; their ``XLA Ops`` line holds
one event per operation the chip ran, named by the operation's HLO text
(``%jvp_jit_kl_loss__.11 = f32[...] custom-call(...)``), of which the
reduction keeps the operation's own name (``jvp_jit_kl_loss__.11``): the
operands that follow would name the ops it consumes.  Busy time is the union of those
intervals inside the traced window; a gap is a stretch of the window with
no operation, labelled by the innermost host span that covers its middle
(the harness's own ``TraceAnnotation``s and the host events JAX records).
"""
from __future__ import annotations

import glob
import os
from dataclasses import dataclass, field
from typing import Dict, List, Optional, Tuple

OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"    # read where a plane has no ops line
WINDOW_SPAN = "campaign"        # the harness's span around the campaign


@dataclass
class Trace:
    window: Tuple[float, float]                       # ns, host clock
    ops: Dict[str, List[Tuple[str, float, float]]]    # device -> events
    host: List[Tuple[str, float, float]] = field(default_factory=list)
    schedule: Optional[tuple] = None                  # (a, E) it ran

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) * 1e-9

    def busy_s(self) -> float:
        """Seconds in which an operation ran, averaged over the devices."""
        if not self.ops:
            return 0.0
        return sum(covered(merge(ev), self.window)
                   for ev in self.ops.values()) * 1e-9 / len(self.ops)

    def op_seconds(self, match) -> Tuple[float, int]:
        """Summed device seconds and count of the events whose name
        ``match`` accepts, averaged over the devices."""
        if not self.ops:
            return 0.0, 0
        t = n = 0
        for ev in self.ops.values():
            for name, s, d in ev:
                if match(name) and self.window[0] <= s < self.window[1]:
                    t += d
                    n += 1
        return t * 1e-9 / len(self.ops), n // len(self.ops)

    def top_ops(self, k: int = 10):
        tot: Dict[str, float] = {}
        for ev in self.ops.values():
            for name, s, d in ev:
                if self.window[0] <= s < self.window[1]:
                    tot[name] = tot.get(name, 0.0) + d
        n = max(len(self.ops), 1)
        top = sorted(tot.items(), key=lambda kv: -kv[1])[:k]
        return [[name, t * 1e-9 / n] for name, t in top]

    def idle_gaps(self, k: int = 10):
        """The k longest stretches of the window in which the first device
        ran nothing, each labelled by what the host was doing."""
        if not self.ops:
            return []
        dev = sorted(self.ops)[0]
        spans = merge(self.ops[dev])
        gaps, t = [], self.window[0]
        for s, e in spans:
            if s > t:
                gaps.append((t, min(s, self.window[1])))
            t = max(t, e)
            if t >= self.window[1]:
                break
        if t < self.window[1]:
            gaps.append((t, self.window[1]))
        gaps = sorted((g for g in gaps if g[1] > g[0]),
                      key=lambda g: g[0] - g[1])[:k]
        return [[self.label(0.5 * (s + e)), (e - s) * 1e-9]
                for s, e in gaps]

    def label(self, t: float) -> str:
        best: Optional[Tuple[float, str]] = None
        for name, s, d in self.host:
            if s <= t < s + d and name != WINDOW_SPAN:
                if best is None or d < best[0]:
                    best = (d, name)
        return best[1] if best else "host outside any span"


def merge(events) -> List[Tuple[float, float]]:
    """Union of the events' [start, end) intervals, sorted."""
    out: List[List[float]] = []
    for s, e in sorted((s, s + d) for _, s, d in events):
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], e)
        else:
            out.append([s, e])
    return [(s, e) for s, e in out]


def covered(spans, window) -> float:
    lo, hi = window
    return sum(max(0.0, min(e, hi) - max(s, lo)) for s, e in spans)


def op_name(text: str) -> str:
    """An operation's own name, from the HLO text of its event."""
    return text.split(" = ", 1)[0].lstrip("%")


def find_xplane(log_dir: str) -> str:
    paths = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"),
                      recursive=True)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {log_dir}")
    return max(paths, key=os.path.getmtime)


def read(path: str, n_devices: int) -> Trace:
    """The first ``n_devices`` TPU planes' operations, the host events, and
    the window: the last ``campaign`` span the harness wrote."""
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(path)
    ops: Dict[str, List[Tuple[str, float, float]]] = {}
    host: List[Tuple[str, float, float]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            idx = int(plane.name.rsplit(":", 1)[1])
            if idx >= n_devices:
                continue
            for line in plane.lines:
                if line.name in (OPS_LINE, MODULES_LINE) and (
                        line.name == OPS_LINE or plane.name not in ops):
                    ops[plane.name] = [(op_name(e.name), float(e.start_ns),
                                        float(e.duration_ns))
                                       for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                host.extend((e.name, float(e.start_ns), float(e.duration_ns))
                            for e in line.events)
    spans = [(s, s + d) for name, s, d in host if name == WINDOW_SPAN]
    if not spans:
        raise ValueError(f"trace {path} has no {WINDOW_SPAN!r} span")
    return Trace(window=max(spans), ops=ops, host=host)
