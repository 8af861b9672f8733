"""The program's spans and named scopes in a traced campaign: what
``tracereduce`` leaves out.

- Idle time by program span: each stretch of the window in which device 0
  ran nothing (as ``Trace.idle_gaps``) goes to the innermost program span
  (``repro.launch.spans.NAMES``, host-plane ``TraceAnnotation``s on the
  device trace's clock) that covers it; time under a campaign's root span
  alone, or under none, is unattributed.
- Device time by named scope: the program names the round's phases
  (``phase_<name>``), its aggregation (``aggregate``) and the eval
  (``eval``) with ``jax.named_scope``, which reaches an op's ``tf_op``
  stat (its HLO ``op_name``, e.g. ``jit(seg)/while/body/phase_client/
  dot_general:``).  JAX's ``ProfileData`` leaves out event metadata stats,
  so ``read_ops`` decodes the trace's XSpace protobuf itself.  Each op
  counts its self time, its duration less what the ops nested in it on the
  same line cover (a ``while`` or ``conditional`` op encloses its body's
  ops), so the scopes' times add up to the busy union.
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Optional, Sequence, Tuple

from tracereduce import OPS_LINE, Trace, merge, op_name

SCOPES = ("aggregate", "eval")        # and every ``phase_<name>``
UNSCOPED = "unscoped"
OP_PATH_STAT = "tf_op"

Op = Tuple[str, float, float, Optional[str]]   # name, start ns, ns, path


# ---------------------------------------------------------------------------
# Idle time by program span
# ---------------------------------------------------------------------------

def idle_stretches(trace: Trace) -> List[Tuple[float, float]]:
    """The stretches of the window in which the first device ran nothing."""
    if not trace.ops:
        return []
    lo, hi = trace.window
    out, t = [], lo
    for s, e in merge(trace.ops[sorted(trace.ops)[0]]):
        if s > t:
            out.append((t, min(s, hi)))
        t = max(t, e)
        if t >= hi:
            break
    if t < hi:
        out.append((t, hi))
    return [(s, e) for s, e in out if e > s]


def idle_by_span(trace: Trace, names: Sequence[str]
                 ) -> Dict[Optional[str], float]:
    """Idle seconds of the first device by the innermost program span (a
    host event named in ``names``) covering them; ``None``: no span."""
    spans = [(s, s + d, name) for name, s, d in trace.host if name in names]
    out: Dict[Optional[str], float] = {}
    for g0, g1 in idle_stretches(trace):
        cuts = sorted({g0, g1} | {t for s, e, _ in spans for t in (s, e)
                                  if g0 < t < g1})
        for a, b in zip(cuts, cuts[1:]):
            mid = 0.5 * (a + b)
            inner = min(((e - s, name) for s, e, name in spans
                         if s <= mid < e), default=(0.0, None))[1]
            out[inner] = out.get(inner, 0.0) + (b - a) * 1e-9
    return out


def idle_unattributed_share(trace: Trace, names: Sequence[str],
                            roots: Sequence[str]) -> Optional[float]:
    """Percent of the first device's idle time that no program step below a
    campaign's root covers; None when the trace holds no program span."""
    if not any(name in names for name, _, _ in trace.host):
        return None
    idle = idle_by_span(trace, names)
    total = sum(idle.values())
    if total <= 0:
        return None
    loose = idle.get(None, 0.0) + sum(idle.get(r, 0.0) for r in roots)
    return 100.0 * loose / total


# ---------------------------------------------------------------------------
# Device time by named scope
# ---------------------------------------------------------------------------

def scope_of(path: Optional[str]) -> Optional[str]:
    """The innermost program scope in an op's ``tf_op`` path, unwrapping
    transforms (``vmap(jvp(phase_client))``); None outside every scope."""
    if not path:
        return None
    found = None
    for part in path.rsplit(":", 1)[0].split("/"):
        while part.endswith(")") and "(" in part:
            part = part[part.index("(") + 1:-1]
        if part.startswith("phase_") or part in SCOPES:
            found = part
    return found


def self_ns(events: Sequence[Op]) -> List[float]:
    """Each event's duration less the part of it that events nested in it
    cover, in the events' order."""
    order = sorted(range(len(events)),
                   key=lambda i: (events[i][1], -events[i][2]))
    own = [float(e[2]) for e in events]
    stack: List[int] = []
    for i in order:
        s, d = events[i][1], events[i][2]
        while stack and events[stack[-1]][1] + events[stack[-1]][2] <= s:
            stack.pop()
        if stack:
            p = stack[-1]
            own[p] -= min(s + d, events[p][1] + events[p][2]) - s
        stack.append(i)
    return own


def scope_seconds(ops: Dict[str, List[Op]], window: Tuple[float, float]
                  ) -> Optional[Dict[str, float]]:
    """Self seconds of the ops that start in ``window``, by innermost scope
    (``UNSCOPED`` outside every scope), averaged over the devices; None
    when no op carries its path, so a missing stat never reads as 0."""
    if not ops or not any(op[3] for ev in ops.values() for op in ev):
        return None
    out: Dict[str, float] = {}
    for ev in ops.values():
        ev = [op for op in ev if window[0] <= op[1] < window[1]]
        for op, own in zip(ev, self_ns(ev)):
            key = scope_of(op[3]) or UNSCOPED
            out[key] = out.get(key, 0.0) + own * 1e-9 / len(ops)
    return out


# ---------------------------------------------------------------------------
# The XSpace protobuf (tsl/profiler/protobuf/xplane.proto), as far as the
# ops' paths need it
# ---------------------------------------------------------------------------

def _varint(buf, i: int) -> Tuple[int, int]:
    x = shift = 0
    while True:
        b = buf[i]
        i += 1
        x |= (b & 0x7F) << shift
        if b < 0x80:
            return x, i
        shift += 7


def _fields(buf) -> Iterator[Tuple[int, object]]:
    """(field number, value) of one message; a length-delimited value is a
    memoryview of its bytes, a varint an int."""
    i, n = 0, len(buf)
    while i < n:
        key, i = _varint(buf, i)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, i = _varint(buf, i)
        elif wire == 2:
            size, i = _varint(buf, i)
            value, i = buf[i:i + size], i + size
        elif wire in (1, 5):
            size = 8 if wire == 1 else 4
            value, i = buf[i:i + size], i + size
        else:
            raise ValueError(f"protobuf wire type {wire} at byte {i}")
        yield number, value


def _text(view) -> str:
    return bytes(view).decode("utf-8", "replace")


def _plane_ops(plane) -> Tuple[str, List[Op]]:
    name, lines, metas, stat_names = "", [], {}, {}
    for f, v in _fields(plane):
        if f == 2:                                   # XPlane.name
            name = _text(v)
        elif f == 3:                                 # lines
            lines.append(v)
        elif f in (4, 5):                            # metadata maps
            entry = dict(_fields(v))
            (metas if f == 4 else stat_names)[entry.get(1, 0)] = entry.get(
                2, b"")
    names = {k: _text(dict(_fields(v)).get(2, b""))
             for k, v in stat_names.items()}
    path_ids = {k for k, v in names.items() if v == OP_PATH_STAT}

    def meta(view) -> Tuple[str, Optional[str]]:
        text, path = "", None
        for f, v in _fields(view):
            if f == 2:                               # XEventMetadata.name
                text = op_name(_text(v))
            elif f == 5:                             # stats
                stat = dict(_fields(v))
                if stat.get(1) in path_ids:
                    path = (_text(stat[5]) if 5 in stat
                            else names.get(stat.get(7)))
        return text, path

    ops: List[Op] = []
    cache: Dict[int, Tuple[str, Optional[str]]] = {}
    for line in lines:
        fields = list(_fields(line))
        if not any(f == 2 and _text(v) == OPS_LINE for f, v in fields):
            continue
        t0 = next((v for f, v in fields if f == 3), 0)   # timestamp_ns
        for f, v in fields:
            if f != 4:                               # events
                continue
            ev = dict(_fields(v))
            mid = ev.get(1, 0)
            if mid not in cache:
                cache[mid] = meta(metas[mid]) if mid in metas else ("", None)
            text, path = cache[mid]
            ops.append((text, t0 + ev.get(2, 0) / 1e3, ev.get(3, 0) / 1e3,
                        path))
    return name, ops


def read_ops(path: str, n_devices: int) -> Dict[str, List[Op]]:
    """The ``XLA Ops`` line of the first ``n_devices`` TPU planes, each op
    with its ``tf_op`` path (None where the op has none), on the clock of
    ``tracereduce.read``."""
    with open(path, "rb") as fh:
        space = memoryview(fh.read())
    out: Dict[str, List[Op]] = {}
    for f, plane in _fields(space):
        if f != 1:
            continue
        head = next((v for g, v in _fields(plane) if g == 2), b"")
        name = _text(head)
        if not name.startswith("/device:TPU:"):
            continue
        if int(name.rsplit(":", 1)[1]) >= n_devices:
            continue
        name, ops = _plane_ops(plane)
        out[name] = ops
    return out
