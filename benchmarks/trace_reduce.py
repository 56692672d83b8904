"""From a profiler trace (``.xplane.pb``) to numbers: the device's busy
time as the union of its operations' intervals, the idle share, the
time per operation name, the time of events whose text holds given
substrings (a kernel), and the longest idle gaps named by what the host was doing.

It knows nothing of any model. It reads the trace with JAX's own
``ProfileData`` and nothing else. On a TPU the device planes are
``/device:TPU:<n>`` and their operations sit on the line ``XLA Ops``
with the HLO text as the event name; host threads are lines of
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear under
their own names.
"""

import glob
import os
import re

WINDOW_SPAN = "bench:window"
_OPS_LINE = "XLA Ops"
_SUFFIX = re.compile(r"\.\d+$")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[0-9,]*\])")


def find_xplane(trace_dir):
    """The one ``.xplane.pb`` a ``jax.profiler`` trace left under
    ``trace_dir`` (None when there is none)."""
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    return paths[-1] if paths else None


def op_label(text):
    """``%copy.5 = f32[4,8,1]{..} copy(...)`` -> ``copy f32[4,8,1]``: the
    operation's name without its running number, with the type of what
    it produces (so a pool-shaped copy can be told from a small one)."""
    name, _, rest = text.partition(" = ")
    name = _SUFFIX.sub("", name.lstrip("%").strip())
    m = _SHAPE.match(rest.strip())
    return "{} {}".format(name, m.group(1)) if m else name


def _union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            if b > out[-1][1]:
                out[-1][1] = b
        else:
            out.append([a, b])
    return out


def _innermost(names, starts, ends, t):
    """Name of the shortest host event covering time ``t``."""
    import numpy as np

    covering = np.nonzero((starts <= t) & (ends >= t))[0]
    if not len(covering):
        return "no host span"
    return names[covering[np.argmin((ends - starts)[covering])]]


def reduce(xplane_path, patterns=None, top=10):
    """Reduce one trace. ``patterns`` maps a key to a list of substrings;
    an event whose HLO text holds ALL of them counts for that key.

    Returns ``{"window_s", "busy_s", "devices", "device_ops",
    "idle_gaps", "matched": {key: {"seconds", "events"}}}`` - seconds
    are averaged over the device planes that ran anything; the window is
    the ``bench:window`` host span where the trace has one, else from
    the first to the last device operation. Returns None where no
    operation ran on a device."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(xplane_path)
    device_lines, host_events, window = [], [], None
    for plane in pd.planes:
        if plane.name.startswith("/device:") and "CUSTOM" not in plane.name:
            for line in plane.lines:
                if line.name == _OPS_LINE:
                    evs = [(e.name, e.start_ns, e.start_ns + e.duration_ns)
                           for e in line.events]
                    if evs:
                        device_lines.append(evs)
        elif plane.name.startswith("/host:CPU"):
            for line in plane.lines:
                for e in line.events:
                    if e.name == WINDOW_SPAN:
                        window = (e.start_ns, e.start_ns + e.duration_ns)
                    else:
                        host_events.append(
                            (e.name, e.start_ns, e.start_ns + e.duration_ns))
    if not device_lines:
        return None
    if window is None:
        window = (min(a for evs in device_lines for _, a, _ in evs),
                  max(b for evs in device_lines for _, _, b in evs))
    w0, w1 = window
    patterns = patterns or {}
    busy_ns, by_label = 0.0, {}
    matched = {k: {"seconds": 0.0, "events": 0} for k in patterns}
    gaps = []
    for d, evs in enumerate(device_lines):
        clipped = []
        for text, a, b in evs:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            clipped.append((a, b))
            label = op_label(text)
            by_label[label] = by_label.get(label, 0.0) + (b - a)
            for k, subs in patterns.items():
                if all(s in text for s in subs):
                    matched[k]["seconds"] += (b - a) * 1e-9
                    matched[k]["events"] += 1
        merged = _union(clipped)
        busy_ns += sum(b - a for a, b in merged)
        if d == 0:
            edge = w0
            for a, b in merged + [[w1, w1]]:
                if a > edge:
                    gaps.append((edge, a))
                edge = max(edge, b)
    n = len(device_lines)
    for k in matched:
        matched[k]["seconds"] /= n
    # name the longest gaps by what the host was doing in their middle
    import numpy as np

    gaps.sort(key=lambda g: g[0] - g[1])
    names = [e[0] for e in host_events]
    starts = np.asarray([e[1] for e in host_events], np.float64)
    ends = np.asarray([e[2] for e in host_events], np.float64)
    named = {}
    for a, b in gaps[:100]:
        name = _innermost(names, starts, ends, (a + b) / 2.0)
        named[name] = named.get(name, 0.0) + (b - a)
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": busy_ns * 1e-9 / n,
        "devices": n,
        "device_ops": [[k, v * 1e-9 / n] for k, v in sorted(
            by_label.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[k, v * 1e-9] for k, v in sorted(
            named.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gap_count": len(gaps),
        "matched": matched}
