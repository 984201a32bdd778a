"""Reduce a profiler trace (``.xplane.pb``) to the numbers the benchmark
reports: device busy time, time per executable and per operation, and the
idle gaps, each attributed to what the harness's host thread was doing.

Device planes are ``/device:TPU:<n>``; on each, the ``XLA Modules`` line
holds one event per executable launch (``jit__step(<fingerprint>)``) and
the ``XLA Ops`` line one event per operation, named by its HLO text
(``%fused_qkv_packed_pallas.8 = bf16[32,2048] custom-call(...)``); the
reduction keeps the instruction name before `` = ``.  A Pallas kernel is a
custom call named after the jitted function that launched it.  Control
flow nests: a ``while`` op spans the ops of its body, so only ops that
hold no other op count as time per op; busy time is the union of all.
Asynchronous copies (``Async XLA Ops``) overlap compute and are left out.
Host spans are the harness's own ``TraceAnnotation`` events on the host
plane.  All times are nanoseconds on the trace's clock.
"""

from __future__ import annotations

import dataclasses
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

MODULE_LINE = "XLA Modules"
OP_LINE = "XLA Ops"


@dataclasses.dataclass
class Event:
    """One trace event: name, start and duration in ns."""

    name: str
    start: int
    dur: int

    @property
    def end(self) -> int:
        return self.start + self.dur


@dataclasses.dataclass
class Reduced:
    """What a trace says about one traced window on each device."""

    window: Tuple[int, int]                 # ns, on the trace clock
    modules: List[List[Event]]              # per device, launch order
    ops: List[List[Event]]                  # per device, start order
    spans: List[Event]                      # harness host spans
    types: Dict[str, str] = dataclasses.field(default_factory=dict)
    # ^ operation name -> its output type, e.g. "s8[32,2048,8,64]"

    def label(self, name: str) -> str:
        """An operation's name with its output type, for reports."""
        t = self.types.get(name)
        return f"{name} {t}" if t else name

    @property
    def window_s(self) -> float:
        return (self.window[1] - self.window[0]) / 1e9

    def busy_s(self) -> float:
        """Seconds in which some operation ran, averaged over devices."""
        if not self.ops:
            return 0.0
        return sum(_union_ns(o, self.window) for o in self.ops) \
            / len(self.ops) / 1e9

    def op_seconds(self) -> Dict[str, float]:
        """Device seconds per operation (ops that hold no other op),
        summed over devices and divided by their count, inside the
        window."""
        out: Dict[str, float] = {}
        for dev in self.ops:
            for e in leaves(dev):
                t = _clip(e, self.window)
                if t > 0:
                    out[e.name] = out.get(e.name, 0.0) + t / 1e9
        n = max(1, len(self.ops))
        return {k: v / n for k, v in out.items()}

    def idle_gaps(self, device: int = 0) -> List[Tuple[int, int]]:
        """Idle intervals of one device inside the window, in order."""
        gaps, cur = [], self.window[0]
        for s, e in _merged(self.ops[device] if self.ops else [],
                            self.window):
            if s > cur:
                gaps.append((cur, s))
            cur = max(cur, e)
        if cur < self.window[1]:
            gaps.append((cur, self.window[1]))
        return gaps

    def idle_by_span(self, device: int = 0) -> Dict[str, float]:
        """Idle seconds of one device, each gap credited to the host span
        that overlaps it most (``other`` where no span does)."""
        out: Dict[str, float] = {}
        for a, b in self.idle_gaps(device):
            best, name = 0, "other"
            for sp in self.spans:
                ov = min(b, sp.end) - max(a, sp.start)
                if ov > best:
                    best, name = ov, sp.name
            out[name] = out.get(name, 0.0) + (b - a) / 1e9
        return out


def _clip(e: Event, window: Tuple[int, int]) -> int:
    return max(0, min(e.end, window[1]) - max(e.start, window[0]))


def _merged(events: Sequence[Event], window: Tuple[int, int]):
    """Union of event intervals clipped to the window, as sorted pairs."""
    out: List[List[int]] = []
    for e in sorted(events, key=lambda e: e.start):
        s, t = max(e.start, window[0]), min(e.end, window[1])
        if t <= s:
            continue
        if out and s <= out[-1][1]:
            out[-1][1] = max(out[-1][1], t)
        else:
            out.append([s, t])
    return out


def _union_ns(events: Sequence[Event], window: Tuple[int, int]) -> int:
    return sum(t - s for s, t in _merged(events, window))


def find_xplane(trace_dir: str) -> Optional[str]:
    """The newest ``.xplane.pb`` under a ``jax.profiler`` trace directory."""
    files = glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                      recursive=True)
    return max(files, key=os.path.getmtime) if files else None


def op_name(text: str) -> str:
    """``%copy.410 = s8[...] copy(...)`` -> ``copy.410``."""
    return text.split(" = ", 1)[0].lstrip("%")


def op_type(text: str) -> str:
    """``%copy.410 = s8[1,32]{1,0:T(8,128)} copy(...)`` -> ``s8[1,32]``
    (the output type without its layout; tuples are shortened)."""
    rhs = text.split(" = ", 1)[1] if " = " in text else ""
    if rhs.startswith("("):
        return "(tuple)"
    return rhs.split("{", 1)[0].split(" ", 1)[0]


def kernel_of(name: str) -> str:
    """An instruction's name without its numeric suffix:
    ``abfp_matmul_packed_pallas.777`` -> ``abfp_matmul_packed_pallas``."""
    head, _, tail = name.rpartition(".")
    return head if head and tail.isdigit() else name


def _events(line, rename=lambda n: n) -> List[Event]:
    return [Event(rename(e.name), int(e.start_ns), int(e.duration_ns))
            for e in line.events]


def leaves(events: Sequence[Event]) -> List[Event]:
    """The events that contain no other event (control-flow ops such as
    ``while`` span the ops of their body)."""
    evs = sorted(events, key=lambda e: (e.start, -e.dur))
    out = []
    for i, e in enumerate(evs):
        nxt = evs[i + 1] if i + 1 < len(evs) else None
        if nxt is None or nxt.start >= e.end or nxt.end > e.end:
            out.append(e)
    return out


def reduce_trace(path: str, window_span: str,
                 span_names: Sequence[str] = ()) -> Reduced:
    """Read ``path`` and reduce it over the host span named
    ``window_span`` (the traced window).  ``span_names`` are the harness
    spans that idle gaps may be credited to."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    modules, ops, spans, window, types = [], [], [], None, {}

    def rename(text: str) -> str:
        name = op_name(text)
        if name not in types:
            types[name] = op_type(text)
        return name

    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            lines = {line.name: line for line in plane.lines}
            modules.append(_events(lines[MODULE_LINE])
                           if MODULE_LINE in lines else [])
            ops.append(_events(lines[OP_LINE], rename)
                       if OP_LINE in lines else [])
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in _events(line):
                    if e.name == window_span:
                        window = (e.start, e.end)
                    elif e.name in span_names:
                        spans.append(e)
    if window is None:
        raise ValueError(f"no host span {window_span!r} in {path}")
    return Reduced(window=window, modules=modules, ops=ops, spans=spans,
                   types=types)
