"""The one reduction from a profiler trace (``.xplane.pb``) to numbers.

``reduce_trace`` gives, for the traced window: the seconds in which an
operation ran on the device (union of the device-op intervals, averaged
over the device planes), the device operations that took most time, and
the longest idle gaps by what the host was doing.  The window is what the
harness's own request spans cover, so the idle time before the first and
after the last device operation counts as a gap too.  A gap is named by
the host span that overlaps it longest and that span gets its overlap;
what is left of the gap is untraced host time (Python: the Python tracer
is off, it would slow the callers).

On a TPU the device planes are ``/device:TPU:<n>`` and the operations are
the events of their ``XLA Ops`` line.  The CPU backend has no device
plane: there (rehearsal only) the host events that carry an ``hlo_op``
stat stand in, as one pseudo-device, so the same code path is exercised.
"""

from __future__ import annotations

import glob
import os

import numpy as np

DEVICE_PLANE = "/device:TPU:"
OPS_LINE = "XLA Ops"
HOST_PLANE = "/host:CPU"
#: the harness's own span around each request; it covers every gap, so
#: it is not an answer to "what was the host doing"
OWN_SPAN = "chipbench.request"
UNTRACED = "host: no traced span (Python)"
TOP = 10  # entries in each breakdown list
GAPS_NAMED = 400  # longest gaps that are looked up among the host spans
NAME_CHARS = 120  # an XLA op's name is its whole HLO line


def find_xplane(trace_dir: str) -> str:
    found = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not found:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return found[-1]


def union_intervals(start: np.ndarray, end: np.ndarray):
    """Merged, sorted (start, end) of possibly overlapping intervals."""
    if start.shape[0] == 0:
        return start, end
    order = np.argsort(start, kind="stable")
    start, end = start[order], np.maximum.accumulate(end[order])
    first = np.ones(start.shape[0], bool)
    first[1:] = start[1:] > end[:-1]
    return start[first], end[np.append(np.nonzero(first)[0][1:] - 1,
                                       start.shape[0] - 1)]


def _events(line):
    """(names, start_ns, end_ns) of one line's events."""
    names, start, dur = [], [], []
    for e in line.events:
        names.append(e.name)
        start.append(e.start_ns)
        dur.append(e.duration_ns)
    start = np.asarray(start, np.float64)
    return names, start, start + np.asarray(dur, np.float64)


def _device_lines(profile):
    """One (names, start, end) per device; see the module docstring."""
    devices, hosts = [], []
    for plane in profile.planes:
        if plane.name.startswith(DEVICE_PLANE):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(_events(line))
        elif plane.name == HOST_PLANE:
            hosts = list(plane.lines)
    if devices:
        return devices, hosts
    names, start, end = [], [], []
    for line in hosts:  # CPU rehearsal: XLA's CPU ops run on host threads
        for e in line.events:
            if e.duration_ns > 0 and any(k == "hlo_op" for k, _ in e.stats):
                names.append(e.name)
                start.append(e.start_ns)
                end.append(e.start_ns + e.duration_ns)
    pseudo = (names, np.asarray(start, np.float64), np.asarray(end, np.float64))
    return ([pseudo] if names else []), hosts


def _top(names, seconds) -> list:
    total: dict = {}
    for n, s in zip(names, seconds):
        total[n[:NAME_CHARS]] = total.get(n[:NAME_CHARS], 0.0) + float(s)
    return [[n, s] for n, s in
            sorted(total.items(), key=lambda kv: -kv[1])[:TOP]]


def _host_spans(hosts):
    """(names, start, end) of the host spans, and the window the harness's
    own request spans cover (None where it recorded none)."""
    names, start, end, own = [], [], [], []
    for line in hosts:
        n, s, e = _events(line)
        mine = np.array([x == OWN_SPAN for x in n], bool)
        own.append((s[mine], e[mine]))
        keep = ~mine & (e > s)
        names += [x for x, k in zip(n, keep) if k]
        start.append(s[keep])
        end.append(e[keep])
    cat = lambda parts: np.concatenate(parts) if parts else np.zeros(0)
    own_start, own_end = cat([o[0] for o in own]), cat([o[1] for o in own])
    window = ((float(own_start.min()), float(own_end.max()))
              if own_start.shape[0] else None)
    return names, cat(start), cat(end), window


def _name_gaps(gap_start, gap_end, names, start, end):
    """(names, seconds): each gap's longest-overlapping host span with its
    overlap, and the rest of the gap as untraced host time."""
    out_names, out_s = [], []
    for g0, g1 in zip(gap_start, gap_end):
        rest = g1 - g0
        if names:
            over = np.minimum(end, g1) - np.maximum(start, g0)
            j = int(np.argmax(over))
            if over[j] > 0:
                out_names.append(names[j])
                out_s.append(over[j] / 1e9)
                rest -= over[j]
        if rest > 0:
            out_names.append(UNTRACED)
            out_s.append(rest / 1e9)
    return out_names, out_s


def reduce_trace(xplane_path: str) -> dict:
    """See the module docstring.  Seconds throughout; ``busy_s`` is None
    where the trace holds no device operation."""
    import jax

    profile = jax.profiler.ProfileData.from_file(xplane_path)
    devices, hosts = _device_lines(profile)
    if not devices:
        return {"busy_s": None, "devices": 0, "device_events": 0,
                "device_ops": [], "idle_gaps": []}
    span_names, span_start, span_end, window = _host_spans(hosts)
    busy, op_names, op_s = [], [], []
    gap_start, gap_end = [], []
    for names, start, end in devices:
        s, e = union_intervals(start, end)
        busy.append(float((e - s).sum()) / 1e9)
        w0, w1 = window or (s[0], e[-1])
        gap_start.append(np.append(min(w0, s[0]), e))
        gap_end.append(np.append(s, max(w1, e[-1])))
        op_names += names
        op_s.append((end - start) / 1e9)
    gap_start, gap_end = np.concatenate(gap_start), np.concatenate(gap_end)
    longest = np.argsort(gap_start - gap_end)[:GAPS_NAMED]
    return {
        "busy_s": sum(busy) / len(busy),
        "devices": len(devices),
        "device_events": len(op_names),
        "device_ops": _top(op_names, np.concatenate(op_s)),
        "idle_gaps": _top(*_name_gaps(gap_start[longest], gap_end[longest],
                                      span_names, span_start, span_end)),
    }
