"""From a profiler trace (``.xplane.pb``) to busy time, every op's and every
scope's device time, and idle gaps.

Read with ``jax.profiler.ProfileData`` alone. A device plane is one whose
name starts with ``/device:TPU:``; its ``XLA Ops`` line holds one event per
executed op and its ``XLA Modules`` line one per executed program. Host
planes (``/host:...``) hold the benchmark's ``TraceAnnotation`` spans on
the same clock.

The program names its layers with ``jax.named_scope``; the compiler writes
that name stack into each instruction's ``op_name`` in the optimized HLO
text, and the device plane names each event by its instruction. Joining the
two gives device time by scope. The join here is the yardstick's own copy of
``deeplearning4j_tpu.runtime.profiler.scope_times`` (the same rules, cut at
the ``measure`` mark and averaged over the device planes), kept where a PR
that claims a gain cannot change it; tests/yardstick holds the two to the
same table.
"""

import bisect
import glob
import os
import re

DEVICE_PREFIX, HOST_PREFIX = "/device:TPU:", "/host:CPU"
OPS_LINE, MODULES_LINE = "XLA Ops", "XLA Modules"
PHASE_TAGS = {"forward": "fwd", "backward": "bwd", "optimizer": "opt"}  # a scope's phase, and its tag in ``device_ops``
_HLO_INSTRUCTION = re.compile(
    r"^\s*(?:ROOT\s+)?%?([\w.\-]+) = .*?metadata=\{op_name=\"([^\"]*)\"", re.M)
_WRAPPED = re.compile(r"^(\w+)\((.*)\)$")
_LAYER_SCOPE = re.compile(r"^[^/()]+\.[A-Za-z_]\w*$")  # <layer key>.<LayerClass>


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


def kind_of(name):
    """Ops of one kind differ in a trailing number only (``fusion.390``)."""
    return name.rstrip("0123456789").rstrip(".")


def split_op_name(op_name):
    """``a/jvp(b)/c`` -> ``[a, jvp(b), c]`` (a ``/`` inside parentheses does not split)."""
    parts, depth, start = [], 0, 0
    for i, ch in enumerate(op_name):
        if ch == "(":
            depth += 1
        elif ch == ")":
            depth -= 1
        elif ch == "/" and depth == 0:
            parts.append(op_name[start:i])
            start = i + 1
    parts.append(op_name[start:])
    return parts


def classify_op_name(op_name):
    """``(phase, scope path)`` of one HLO ``op_name``, or ``None`` when it
    carries no scope of the program's.

    JAX writes the name stack as ``jit(step)/jvp(layer_3.Block)/qkv/dot_general``:
    a component wrapped in ``transpose(...)`` is the backward pass, ``jvp(...)``
    alone the forward pass; ``jit(...)`` components and the last one (the
    primitive) are no scopes. A path counts as the program's when it starts
    with ``loss``, ``updater`` or a ``<layer key>.<LayerClass>`` scope."""
    path, backward = [], False
    for part in split_op_name(op_name)[:-1]:
        wrappers = []
        while (m := _WRAPPED.match(part)):
            wrappers.append(m.group(1))
            part = m.group(2)
        if "jit" in wrappers or "pjit" in wrappers:
            continue
        backward = backward or "transpose" in wrappers
        if part:
            path.append(part)
    if not path or not (path[0] in ("loss", "updater") or _LAYER_SCOPE.match(path[0])):
        return None
    return ("backward" if backward else "optimizer" if path[0] == "updater" else "forward"), tuple(path)


def scope_table(program, op_ns, runs, step_ns, hlo_texts, depth=2):
    """Seconds a run of the step program by phase and scope.

    ``op_ns``: nanoseconds by instruction name, summed over ``runs`` whole
    runs of ``program`` that took ``step_ns`` together. Each instruction's
    ``op_name`` is looked up in the HLO text whose instruction names cover
    most of these ops. A fusion goes whole to the scope in its own metadata
    (Adam's update fused into a weight-gradient matmul is booked under that
    layer's backward pass). The ``<layer key>.`` of a path's first scope is
    dropped, so that the blocks of one class add up; paths are cut to
    ``depth``. ``phases`` sum to ``step_s``: ``other`` is what no scope
    covers, ops and gaps alike.

    Raises ``RuntimeError`` when no op carries a scope: shares of nothing
    are not printed."""
    op_names = max((dict(_HLO_INSTRUCTION.findall(text)) for text in hlo_texts),
                   key=lambda table: len(op_ns.keys() & table.keys()), default={})
    scopes, unattributed = {phase: {} for phase in PHASE_TAGS}, {}
    for name, ns in op_ns.items():
        op_name = op_names.get(name, "")
        found = classify_op_name(op_name)
        if found is None:
            primitive = split_op_name(op_name)[-1]
            key = f"{kind_of(name)} [{primitive}]" if primitive else kind_of(name)
            unattributed[key] = unattributed.get(key, 0) + ns
        else:
            phase, path = found
            if _LAYER_SCOPE.match(path[0]):
                path = (path[0].rsplit(".", 1)[1],) + path[1:]
            key = "/".join(path[:depth])
            scopes[phase][key] = scopes[phase].get(key, 0) + ns
    if not any(scopes.values()):
        raise RuntimeError(
            f"trace_reduce: none of the {len(op_ns)} device ops of {program} carries a scope of the "
            "program's (layer_N.Class, loss, updater). Either the executable came out of a compile cache "
            "written before the scopes were (the cache key leaves op_name out: clear the cache), or the "
            f"HLO text handed in is not this program's ({len(op_ns.keys() & op_names.keys())} of "
            f"{len(op_ns)} instruction names found in it)")

    def per_run(table):
        return {k: v / runs / 1e9 for k, v in sorted(table.items(), key=lambda kv: -kv[1])}

    step_s = step_ns / runs / 1e9
    phases = {phase: sum(table.values()) / runs / 1e9 for phase, table in scopes.items()}
    attributed = sum(phases.values())
    phases["other"] = step_s - attributed
    return {"program": program, "runs": runs, "step_s": step_s, "phases": phases,
            "scopes": {phase: per_run(table) for phase, table in scopes.items()},
            "unattributed": per_run(unattributed), "attributed_fraction": attributed / step_s}


def scope_share(scopes, names, phases=("forward", "backward")):
    """Per cent of the step program's mean device time under the scopes whose
    last component is one of ``names``, over ``phases``; ``None`` where the
    trace has no scope table or no such scope ran (a reader's ``read`` can
    return it as it is)."""
    if not scopes:
        return None
    seconds = sum(s for phase in phases for scope, s in scopes["scopes"][phase].items()
                  if scope.rsplit("/", 1)[-1] in names)
    return 100.0 * seconds / scopes["step_s"] if seconds else None


def reduce_planes(planes, hlo_texts=None, top=5, gaps=5, labels=("feed",), default_label="fit",
                  since="measure"):
    """``planes``: [(plane name, [(line name, [(event name, start_ns, duration_ns)])])];
    ``hlo_texts``: the optimized HLO text of the candidate step programs, or
    ``None`` where the runner has none to give.

    Everything before the start of the host event named ``since`` is cut off:
    the profiler's own start-up is no part of the traced window. Returns, per
    device (sums over the device planes divided by their number):

    - ``busy_s``: the union of the op intervals;
    - ``op_seconds``: ``{op name: seconds}`` for every op, and
      ``kind_seconds``: ``{kind: [seconds, runs]}`` for every kind of op (an
      op the mark fell into counts by its part after it, in both);
    - ``program``: the program that took most device time (the step), with
      ``program_runs`` (the run the mark fell into by its part) and
      ``program_mean_s``, the whole runs' mean seconds;
    - ``scopes`` (with ``hlo_texts``; else ``None``): ``scope_table`` over the
      ops inside the step program's whole runs after the mark, on every
      device plane;
    - ``device_ops``: with scopes, the ``top`` scopes by their seconds in
      those whole runs (``Class/scope fwd|bwd|opt``), then the ``top`` kinds
      of op as ``kind.*xN`` (N ops of that kind); without, the kinds and then
      the ``top`` single ops;
    - ``idle_gaps``: the ``gaps`` longest idle gaps of the first device, each
      labelled with the host annotation (one of ``labels``) that covers most
      of it, else with ``default_label``; ``gap_hosts``: for the earlier
      output lines, the host events that overlap each gap most.
    """
    devices = sorted((n, dict(lines)) for n, lines in planes if n.startswith(DEVICE_PREFIX))
    if not devices:
        return None
    host = [(name, s, s + d) for n, lines in planes if not n.startswith(DEVICE_PREFIX)
            for _, events in lines for name, s, d in events]
    t0 = min((s for name, s, _ in host if name == since), default=None)
    if t0 is None:
        return None  # no mark: the window cannot be placed on the trace's clock
    spans = {label: merge((s, e) for name, s, e in host if name == label) for label in labels}
    busy_ns, op_ns, kind_ns, kind_runs, kind_names, first_gaps = 0, {}, {}, {}, {}, []
    module_ns, module_runs, module_part = {}, {}, {}
    for index, (_, lines) in enumerate(devices):
        ops = [(short(name), max(s, t0), s + d, d) for name, s, d in lines.get(OPS_LINE, []) if s + d > t0]
        merged = merge((s, e) for _, s, e, _ in ops)
        busy_ns += sum(e - s for s, e in merged)
        for name, s, d in lines.get(MODULES_LINE, []):
            if s >= t0:
                module_ns[name] = module_ns.get(name, 0) + d
                module_runs.setdefault(name, []).append((index, s, s + d))
            elif s + d > t0:  # the run the mark fell into counts by its part after it
                module_part[name] = module_part.get(name, 0) + (s + d - t0) / d
        for name, s, e, d in ops:
            kind = kind_of(name)
            op_ns[name] = op_ns.get(name, 0) + e - s
            kind_ns[kind] = kind_ns.get(kind, 0) + e - s
            kind_runs[kind] = kind_runs.get(kind, 0) + ((e - s) / d if d else 1)
            kind_names.setdefault(kind, set()).add(name)
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
    runs = module_runs.get(step, [])
    scopes, scope_rows = None, []
    if hlo_texts is not None and runs:
        in_runs = {}  # by instruction, over the step program's whole runs after the mark
        for index, (_, lines) in enumerate(devices):
            mine = sorted((s, e) for i, s, e in runs if i == index)
            starts = [s for s, _ in mine]
            for name, s, d in lines.get(OPS_LINE, []):
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s + d <= mine[i][1]:
                    name = short(name)
                    in_runs[name] = in_runs.get(name, 0) + d
        scopes = scope_table(step, in_runs, len(runs), module_ns[step], hlo_texts)
        scope_rows = ranked({f"{scope} {PHASE_TAGS[phase]}": seconds * len(runs) / n
                             for phase, table in scopes["scopes"].items() for scope, seconds in table.items()})
    rows = ([[name, seconds] for name, seconds in scope_rows]
            + [[f"{kind}.*x{len(kind_names[kind])}", ns / 1e9 / n] for kind, ns in ranked(kind_ns)]
            + [[name, ns / 1e9 / n] for name, ns in ranked(op_ns)])
    return {"busy_s": busy_ns / 1e9 / n, "devices": n, "program": step,
            "program_runs": (len(runs) + module_part.get(step, 0)) / n,
            "program_mean_s": module_ns[step] / len(runs) / 1e9 if runs else None,
            "op_seconds": {name: ns / 1e9 / n for name, ns in op_ns.items()},
            "kind_seconds": {kind: [ns / 1e9 / n, kind_runs[kind] / n] for kind, ns in kind_ns.items()},
            "scopes": scopes, "device_ops": rows[:2 * top], "idle_gaps": labelled, "gap_hosts": gap_hosts}


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


def reduce_dir(trace_dir, hlo_texts=None, **kw):
    planes = load(trace_dir)
    return None if planes is None else reduce_planes(planes, hlo_texts, **kw)
