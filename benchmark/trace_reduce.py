"""From a profiler trace (``.xplane.pb``) to busy time, top ops and idle gaps.

Read with ``jax.profiler.ProfileData`` alone. A device plane is one whose
name starts with ``/device:TPU:``; its ``XLA Ops`` line holds one event per
executed op and its ``XLA Modules`` line one per executed program. Host
planes (``/host:...``) hold the benchmark's ``TraceAnnotation`` spans on
the same clock.
"""

import glob
import os

DEVICE_PREFIX, HOST_PREFIX = "/device:TPU:", "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"


def merge(intervals):
    """Union of (start, end) intervals as a sorted list of disjoint ones."""
    out = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            out[-1][1] = max(out[-1][1], end)
        else:
            out.append([start, end])
    return out


def overlap(start, end, merged):
    return sum(max(0, min(end, e) - max(start, s)) for s, e in merged)


def short(name):
    """An op's name as the trace gives it, without its HLO text."""
    return name.split(" = ")[0].lstrip("%")


def reduce_planes(planes, top=5, gaps=5, labels=("feed",), default_label="fit", since="measure"):
    """``planes``: [(plane name, [(line name, [(event name, start_ns, duration_ns)])])].

    Everything before the start of the host event named ``since`` is cut off:
    the profiler's own start-up is no part of the traced window.
    Returns busy seconds averaged over the device planes; ``device_ops``: the
    ``top`` kinds of op by total time (ops of one kind differ in a trailing
    number only, and are shown as ``kind.*xN``), then the ``top`` single ops;
    the program that took most device time (the step) with its runs per
    device (the one the mark fell into by its part) and the whole runs' mean
    seconds; and the ``gaps`` longest idle gaps of
    the first device, each labelled with the host annotation (one of
    ``labels``) that covers most of it, else with ``default_label``, and for
    the earlier output lines the host events that overlap each gap most.
    """
    devices = [(n, dict(lines)) for n, lines in planes if n.startswith(DEVICE_PREFIX)]
    if not devices:
        return None
    host = [(name, s, s + d) for n, lines in planes if not n.startswith(DEVICE_PREFIX)
            for _, events in lines for name, s, d in events]
    t0 = min((s for name, s, _ in host if name == since), default=None)
    if t0 is None:
        return None  # no mark: the window cannot be placed on the trace's clock
    spans = {label: merge((s, e) for name, s, e in host if name == label) for label in labels}
    busy_ns, op_ns, kind_ns, kind_n, first_gaps = 0, {}, {}, {}, []
    module_ns, module_runs, module_part = {}, {}, {}
    for index, (_, lines) in enumerate(sorted(devices)):
        ops = [(name, max(s, t0), s + d) for name, s, d in lines.get(OPS_LINE, []) if s + d > t0]
        merged = merge((s, e) for _, s, e in ops)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= t0:
                module_ns[name] = module_ns.get(name, 0) + d
                module_runs[name] = module_runs.get(name, 0) + 1
            elif s + d > t0:  # the run the mark fell into counts by its part after it
                module_part[name] = module_part.get(name, 0) + (s + d - t0) / d
        for name, s, e in ops:
            name = short(name)
            kind = name.rstrip("0123456789").rstrip(".")
            op_ns[name] = op_ns.get(name, 0) + e - s
            kind_ns[kind] = kind_ns.get(kind, 0) + e - s
            kind_n.setdefault(kind, set()).add(name)
        if index == 0:
            first_gaps = sorted(((b[0] - a[1], a[1], b[0]) for a, b in zip(merged, merged[1:])),
                                reverse=True)[:gaps]
    n = len(devices)
    labelled, gap_hosts = [], []
    for length, start, end in first_gaps:
        covered = {label: overlap(start, end, merged) for label, merged in spans.items()}
        best = max(covered, key=covered.get) if covered else default_label
        labelled.append([best if covered.get(best, 0) * 2 > length else default_label, length / 1e9])
        over = sorted(((min(end, e) - max(start, s), name) for name, s, e in host if s < end and e > start),
                      reverse=True)[:4]
        gap_hosts.append({"at_s": (start - t0) / 1e9, "s": length / 1e9,
                          "host": [[short(name)[:60], ns / 1e9] for ns, name in over]})

    def ranked(table):
        return sorted(table.items(), key=lambda kv: kv[1], reverse=True)[:top]

    step = max(module_ns, key=module_ns.get) if module_ns else None
    runs = module_runs.get(step, 0)
    return {"busy_s": busy_ns / 1e9 / n, "devices": n, "program": step, "program_runs": (runs + module_part.get(step, 0)) / n,
            "program_mean_s": module_ns[step] / runs / 1e9 if runs else None,
            "device_ops": [[f"{kind}.*x{len(kind_n[kind])}", ns / 1e9 / n] for kind, ns in ranked(kind_ns)]
                          + [[name, ns / 1e9 / n] for name, ns in ranked(op_ns)],
            "idle_gaps": labelled, "gap_hosts": gap_hosts}


def load(trace_dir):
    """The newest ``.xplane.pb`` under ``trace_dir`` as ``reduce_planes`` takes
    it: the device planes' op and program lines and the host's threads, names
    cut to ``short`` (an op's full HLO text runs to kilobytes)."""
    from jax.profiler import ProfileData
    paths = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"), recursive=True),
                   key=os.path.getmtime)
    if not paths:
        return None
    data = ProfileData.from_file(paths[-1])
    planes = []
    for plane in data.planes:
        device = plane.name.startswith(DEVICE_PREFIX)
        if not device and not plane.name.startswith(HOST_PREFIX):
            continue
        planes.append((plane.name, [(line.name, [(short(e.name), e.start_ns, e.duration_ns) for e in line.events])
                                    for line in plane.lines if not device or line.name in (OPS_LINE, MODULES_LINE)]))
    return planes


def reduce_dir(trace_dir, **kw):
    planes = load(trace_dir)
    return None if planes is None else reduce_planes(planes, **kw)
