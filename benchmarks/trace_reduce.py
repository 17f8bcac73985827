"""From a profiler trace to device-busy time, per-program time, the
device operations that took most time, and the idle gaps by what the
host was doing.

Two steps, so that the arithmetic can be checked on hand-made numbers:
:func:`extract` reads an ``.xplane.pb`` (with nothing but JAX) into
plain lists of ``[name, start_ns, duration_ns]``; :func:`reduce` turns
such lists into the summary.  Nothing here imports the program.

What a TPU trace looks like (my chip run, PR 25; ``trace_listing`` in
``benchmarks/fixtures``): one plane per chip named ``/device:TPU:<n>``
whose line ``XLA Modules`` has one event per run of a compiled program,
named ``<module name>(<fingerprint>)``, and whose line ``XLA Ops`` has
one event per device operation; host threads are lines of the plane
``/host:CPU``, where ``jax.profiler.TraceAnnotation`` spans appear under
the name they were given.  All planes share one clock.
"""

import glob
import os
import re
import statistics

#: the driver's own host spans carry this prefix in the trace
SPAN_PREFIX = "bench:"
WINDOW_SPAN = SPAN_PREFIX + "window"

MODULE_LINE = "XLA Modules"
OPS_LINE = "XLA Ops"
_FINGERPRINT = re.compile(r"\(\d+\)$")
_OPCODE = re.compile(r" ([a-z][\w\-]*)\(")
_SHAPE = re.compile(r"^\(?([a-z0-9]+\[[^\]]*\])")
#: operations that only hold others (a loop's event spans its body's):
#: counted as busy time, left out of the ranking of operations
_CONTAINERS = ("while", "conditional", "call")


def short_op_name(name):
    """``%fusion.3 fusion bf16[256,55,55,96]`` from the HLO instruction
    text the trace names a device operation by (which runs to kilobytes):
    its identifier, its opcode and the (first) result shape."""
    head, sep, rest = name.partition(" = ")
    if not sep:
        return name[:120]
    opcode = _OPCODE.search(" " + rest)
    shape = _SHAPE.match(rest)
    return " ".join(part for part in (
        head, opcode.group(1) if opcode else "",
        shape.group(1) if shape else "") if part)[:120]


def is_container(short_name):
    parts = short_name.split(" ")
    return len(parts) > 1 and parts[1] in _CONTAINERS


def find_xplane(log_dir):
    paths = sorted(glob.glob(os.path.join(
        log_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError("no .xplane.pb under %s" % log_dir)
    return paths[-1]


def extract(path):
    """``{"devices": {plane name: {"modules": [...], "ops": [...]}},
    "spans": [...]}``, every entry ``[name, start_ns, duration_ns]``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    devices, spans = {}, []
    for plane in data.planes:
        if plane.name.startswith("/device:"):
            entry = {"modules": [], "ops": []}
            for line in plane.lines:
                if line.name == MODULE_LINE:
                    target = entry["modules"]
                elif line.name == OPS_LINE:
                    target = entry["ops"]
                else:
                    continue
                shorten = short_op_name if target is entry["ops"] \
                    else str
                for event in line.events:
                    target.append([shorten(event.name),
                                   int(event.start_ns),
                                   int(event.duration_ns)])
            if entry["ops"] or entry["modules"]:
                devices[plane.name] = entry
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for event in line.events:
                    if event.name.startswith(SPAN_PREFIX):
                        spans.append([event.name, int(event.start_ns),
                                      int(event.duration_ns)])
    return {"devices": devices, "spans": spans}


def listing(path, limit=12):
    """Planes, lines and the first event names of a trace: what to look
    at by hand before trusting :func:`extract` on a new device."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    out = []
    for plane in data.planes:
        for line in plane.lines:
            events = list(line.events)
            out.append({"plane": plane.name, "line": line.name,
                        "events": len(events),
                        "first": [e.name[:80] for e in events[:limit]]})
    return out


def merge(intervals):
    """Union of ``(start, end)`` intervals, sorted and disjoint."""
    merged = []
    for start, end in sorted(intervals):
        if merged and start <= merged[-1][1]:
            if end > merged[-1][1]:
                merged[-1][1] = end
        else:
            merged.append([start, end])
    return merged


def idle_between(busy, lo, hi):
    """``[(start, end)]`` of ``[lo, hi)`` that the sorted, disjoint
    ``busy`` intervals leave uncovered."""
    gaps, cursor = [], lo
    for start, end in list(busy) + [[hi, hi]]:
        if start > cursor:
            gaps.append((cursor, start))
        cursor = max(cursor, end)
    return gaps


def label_gaps(gaps, spans, prefix):
    """``{label: ns}`` of the idle ``gaps`` (sorted and disjoint), each
    put down to the shortest span that covers at least half of it (it
    says most about the gap); failing that, to the span that covers
    most of it; ``unattributed`` where no span reaches it.  A span is a
    sequence whose first three items are name, start and duration; its
    label is the name less ``prefix``.  Of spans that tie, the first in
    ``spans`` wins.

    One sweep: the spans in order of start, and beside the current gap
    only those that overlap it, so the cost is the spans and the
    overlaps, not spans x gaps."""
    order = sorted(range(len(spans)), key=lambda i: spans[i][1])
    labelled, active, ahead = {}, [], 0
    for start, end in gaps:
        while ahead < len(order) and spans[order[ahead]][1] < end:
            active.append(order[ahead])
            ahead += 1
        active = [i for i in active if spans[i][1] + spans[i][2] > start]
        best, best_key = "unattributed", None
        for i in active:
            name, s_start, s_dur = spans[i][:3]
            cover = min(end, s_start + s_dur) - max(start, s_start)
            if cover <= 0:
                continue
            key = (2 * cover >= end - start, -s_dur, cover, -i)
            if best_key is None or key > best_key:
                best, best_key = name[len(prefix):], key
        labelled[best] = labelled.get(best, 0) + (end - start)
    return labelled


def _clip(events, lo, hi):
    for name, start, duration in events:
        end = start + duration
        if end <= lo or start >= hi:
            continue
        yield name, max(start, lo), min(end, hi)


def program_name(event_name):
    return _FINGERPRINT.sub("", event_name)


def reduce(extracted, top=10):
    """The summary of one traced window.

    The window is the driver's ``bench:window`` span when the trace has
    one, else from the first to the last device event.  ``busy_s`` is
    the union of the device-operation intervals inside the window,
    averaged over the chips; the first chip's idle gaps are labelled by
    the driver's spans (:func:`label_gaps`)."""
    devices = extracted["devices"]
    if not devices:
        raise ValueError("the trace has no device plane with events")
    spans = extracted["spans"]
    window = [s for s in spans if s[0] == WINDOW_SPAN]
    if window:
        lo = window[0][1]
        hi = lo + window[0][2]
    else:
        every = [e for d in devices.values() for e in d["ops"] + d["modules"]]
        lo = min(e[1] for e in every)
        hi = max(e[1] + e[2] for e in every)
    if hi <= lo:
        raise ValueError("empty traced window")

    busy_ns = []
    per_op = {}
    programs = {}
    for index, name in enumerate(sorted(devices)):
        device = devices[name]
        ops = list(_clip(device["ops"] or device["modules"], lo, hi))
        merged = merge((s, e) for _n, s, e in ops)
        busy_ns.append(sum(e - s for s, e in merged))
        for op, start, end in ops:
            if not is_container(op):
                per_op[op] = per_op.get(op, 0) + (end - start)
        for event_name, start, duration in device["modules"]:
            if start < lo or start + duration > hi:
                continue        # a run cut by the window's edge
            programs.setdefault(program_name(event_name),
                                []).append(duration)
        if index == 0:
            gaps = idle_between(merged, lo, hi)

    others = [s for s in spans if s[0] != WINDOW_SPAN]
    labelled = label_gaps(gaps, others, SPAN_PREFIX)

    by_span = {}
    for name, start, duration in others:
        if start >= lo and start + duration <= hi:
            entry = by_span.setdefault(name[len(SPAN_PREFIX):],
                                       {"count": 0, "total_s": 0.0})
            entry["count"] += 1
            entry["total_s"] += duration / 1e9

    n_chips = len(devices)
    summary = {
        "spans": by_span,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(busy_ns) / n_chips / 1e9,
        "chips": n_chips,
        "programs": {
            name: {"count": len(runs),
                   "total_s": sum(runs) / n_chips / 1e9,
                   "mean_s": statistics.fmean(runs) / 1e9,
                   "median_s": statistics.median(runs) / 1e9}
            for name, runs in programs.items()},
        "device_ops": [[name, ns / n_chips / 1e9] for name, ns in sorted(
            per_op.items(), key=lambda kv: -kv[1])[:top]],
        "idle_gaps": [[name, ns / 1e9] for name, ns in sorted(
            labelled.items(), key=lambda kv: -kv[1])[:top]],
    }
    return summary
