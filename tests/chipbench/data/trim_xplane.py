"""Trim a chip trace to a test fixture of a few hundred KB.

    python tests/chipbench/data/trim_xplane.py <in.xplane.pb> <out.xplane.pb> [n]

Keeps, of a trace that ``chipbench.run.serve(..., trace=True,
trace_dir=<dir>)`` recorded on the chip, the first
``n`` (default 2) whole decode-step launches inside the harness's traced
window: the device's module and op events in that interval (without their
stats), the host events that overlap it, and the window span itself,
clipped to it.  Needs TensorFlow's ``xplane_pb2`` (the benchmark itself
reads traces with ``jax.profiler.ProfileData`` only).
"""

import sys

WINDOW = "chipbench_traced_window"


def _abs_ns(line, ev):
    return line.timestamp_ns + ev.offset_ps / 1000.0


def trim(src: str, dst: str, n_steps: int = 2) -> None:
    from tensorflow.tsl.profiler.protobuf import xplane_pb2

    xs = xplane_pb2.XSpace()
    with open(src, "rb") as f:
        xs.ParseFromString(f.read())
    dev = next(p for p in xs.planes if p.name.startswith("/device:TPU:"))
    host = next(p for p in xs.planes if p.name.startswith("/host:CPU"))
    win = None
    for line in host.lines:
        for ev in line.events:
            if host.event_metadata[ev.metadata_id].name == WINDOW:
                win = (_abs_ns(line, ev), _abs_ns(line, ev)
                       + ev.duration_ps / 1000.0)
    mods = next(ln for ln in dev.lines if ln.name == "XLA Modules")
    steps = [(_abs_ns(mods, e), _abs_ns(mods, e) + e.duration_ps / 1000.0)
             for e in mods.events
             if "_step" in dev.event_metadata[e.metadata_id].name
             and _abs_ns(mods, e) >= win[0]]
    a, b = steps[0][0] - 1000.0, steps[n_steps - 1][1] + 1000.0

    def keep(line, pred):
        evs = [e for e in line.events if pred(line, e)]
        del line.events[:]
        line.events.extend(evs)

    for line in list(dev.lines):
        if line.name not in ("XLA Modules", "XLA Ops"):
            dev.lines.remove(line)
            continue
        keep(line, lambda ln, e: a <= _abs_ns(ln, e)
             and _abs_ns(ln, e) + e.duration_ps / 1000.0 <= b)
        for e in line.events:
            del e.stats[:]
    for line in host.lines:
        keep(line, lambda ln, e: _abs_ns(ln, e) < b
             and _abs_ns(ln, e) + e.duration_ps / 1000.0 > a)
        for e in line.events:
            if host.event_metadata[e.metadata_id].name == WINDOW:
                e.offset_ps = int((a - line.timestamp_ns) * 1000)
                e.duration_ps = int((b - a) * 1000)
    for plane in (dev, host):
        used = {e.metadata_id for ln in plane.lines for e in ln.events}
        for k in [k for k in plane.event_metadata if k not in used]:
            del plane.event_metadata[k]
    for plane in [p for p in xs.planes if p not in (dev, host)]:
        xs.planes.remove(plane)
    with open(dst, "wb") as f:
        f.write(xs.SerializeToString())


if __name__ == "__main__":
    trim(sys.argv[1], sys.argv[2], int(sys.argv[3]) if len(sys.argv) > 3
         else 2)
