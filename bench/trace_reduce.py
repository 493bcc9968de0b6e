"""From a profiler trace to device busy time, idle gaps and top operations.

``extract`` reads the ``.xplane.pb`` that ``jax.profiler`` writes and
returns plain interval lists on the trace's own clock (seconds from the
start of the trace): the operations each device ran, and the host spans
the benchmark annotated.  Everything after that is arithmetic on those
lists, kept apart so that it can be tested without a device.

Device operations are the events of the ``XLA Ops`` line of each
``/device:TPU:<i>`` plane.  On the CPU backend there is no device plane;
the XLA client's thread lines of ``/host:CPU`` stand in, which is good
for testing the arithmetic and never a device number.
"""

from __future__ import annotations

from pathlib import Path

WINDOW_SPAN = "window"


def _newest_xplane(trace_dir: str) -> Path:
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"),
                   key=lambda p: p.stat().st_mtime)
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def extract(trace_dir: str, platform: str, span_names: set[str]
            ) -> tuple[dict[str, list], list]:
    """``({device: [(op, start_s, end_s)]}, [(span, start_s, end_s)])``."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(str(_newest_xplane(trace_dir)))
    ops: dict[str, list] = {}
    spans: list = []
    seen = []
    for plane in data.planes:
        seen.append(f"{plane.name}: " + ", ".join(ln.name for ln in plane.lines))
        device = plane.name.startswith("/device:TPU:")
        host = plane.name == "/host:CPU"
        if not (device or host):
            continue
        for line in plane.lines:
            if device and line.name == "XLA Ops":
                out = ops.setdefault(plane.name, [])
            elif (host and platform == "cpu"
                  and line.name.startswith("tf_XLAPjRtCpuClient")):
                out = ops.setdefault(line.name, [])
            else:
                out = None
            for ev in line.events:
                t0 = ev.start_ns * 1e-9
                t1 = t0 + ev.duration_ns * 1e-9
                if out is not None:
                    if (ev.duration_ns > 0 and not ev.name.startswith("end: ")
                            and not ev.name.startswith("Threadpool")):
                        out.append((ev.name, t0, t1))
                elif host and ev.name in span_names:
                    spans.append((ev.name, t0, t1))
    if not any(ops.values()):
        raise ValueError("the trace holds no device operation; planes: "
                         + " | ".join(seen)[:2000])
    if platform == "cpu":
        # the CPU client's threads stand for one device
        ops = {"cpu": sorted(o for lst in ops.values() for o in lst)}
    return ops, spans


def union(intervals) -> list[tuple[float, float]]:
    """Merge ``(start, end)`` intervals into disjoint sorted ones."""
    out: list[list[float]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def clip(intervals, lo: float, hi: float) -> list[tuple[float, float]]:
    return [(max(a, lo), min(b, hi)) for a, b in intervals
            if b > lo and a < hi]


def length(intervals) -> float:
    return sum(b - a for a, b in intervals)


def intersect(xs, ys) -> list[tuple[float, float]]:
    """Intersection of two sets of disjoint sorted intervals."""
    out, i, j = [], 0, 0
    while i < len(xs) and j < len(ys):
        a, b = max(xs[i][0], ys[j][0]), min(xs[i][1], ys[j][1])
        if a < b:
            out.append((a, b))
        if xs[i][1] < ys[j][1]:
            i += 1
        else:
            j += 1
    return out


def window_of(spans) -> tuple[float, float]:
    """The measured window, as its ``window`` span marks it."""
    marks = [(a, b) for name, a, b in spans if name == WINDOW_SPAN]
    if len(marks) != 1:
        raise ValueError(f"expected one {WINDOW_SPAN!r} span, found "
                         f"{len(marks)}")
    return marks[0]


def reduce(ops: dict[str, list], spans: list, top: int = 10,
           busy_spans: tuple[str, ...] = ()) -> dict:
    """Busy and idle time of the window, averaged over devices.

    ``busy_s``: the union of the intervals in which an operation ran.
    ``idle_gaps``: the longest stretches with no operation, each named by
    the host span (other than the window) that overlaps it most.
    ``device_ops``: operations by total time.  ``busy_in_spans_s``: busy
    time that falls inside the spans named in ``busy_spans``.
    """
    w0, w1 = window_of(spans)
    window_s = w1 - w0
    host = [(n, a, b) for n, a, b in spans if n != WINDOW_SPAN]
    chosen = union((a, b) for n, a, b in host if n in busy_spans)
    busy, busy_in, by_op, gaps = [], [], {}, []
    for events in ops.values():
        merged = union(clip([(a, b) for _, a, b in events], w0, w1))
        busy.append(length(merged))
        busy_in.append(length(intersect(merged, chosen)))
        for name, a, b in events:
            d = min(b, w1) - max(a, w0)
            if d > 0:
                by_op[name] = by_op.get(name, 0.0) + d
        edges = [w0] + [t for iv in merged for t in iv] + [w1]
        for a, b in zip(edges[0::2], edges[1::2]):
            if b > a:
                gaps.append((a, b))
    named = []
    for a, b in sorted(gaps, key=lambda g: g[0] - g[1])[:top]:
        best, label = 0.0, "no span"
        for n, s0, s1 in host:
            over = min(b, s1) - max(a, s0)
            if over > best:
                best, label = over, n
        named.append([label, b - a])
    ndev = len(ops)
    busy_s = sum(busy) / ndev
    return {
        "window_s": window_s,
        "busy_s": busy_s,
        "idle_share": 1.0 - busy_s / window_s,
        "busy_in_spans_s": sum(busy_in) / ndev,
        "device_ops": sorted(([n, t / ndev] for n, t in by_op.items()),
                             key=lambda x: -x[1])[:top],
        "idle_gaps": named,
    }
