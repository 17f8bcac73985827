"""The program's own names in a profiler trace: its host spans
(``veles:<cat>/<name>``, from ``veles_tpu.trace.span``) and the scopes
and kernel names on the device's operations (``veles.<...>`` from
``jax.named_scope``, ``veles_<kernel>`` from ``pallas_call(name=)``).

Two steps, like ``trace_reduce``: :func:`extract` reads the
``.xplane.pb`` the harness wrote (with nothing but JAX) into plain
lists; everything after it is arithmetic on those lists, checked in
``tests/benchmark`` on hand-made ones.  Nothing here imports the
program, and a trace of a program that has no such name (the parent
of the PR that added them) gives empty lists, not an error.

What the names look like in a TPU trace (my chip run, PR 26; TPU v5
lite, JAX 0.9.0; fixtures ``program_trace_<cell>.json``):

* A host span is an event of plane ``/host:CPU`` on the line of the
  thread that ran it, named as given; its keyword arguments are the
  event's ``stats`` (ints stay ints, everything else is a string).
  Two lines may have the same name (every Python thread's line is
  called ``python``), so a thread is the line's index in the plane.
* A device operation is an event of line ``XLA Ops`` of plane
  ``/device:TPU:<n>``, named by its whole HLO instruction text.  The
  scope is NOT in that text: it is in the stat ``tf_op``, which holds
  the instruction's ``op_name`` metadata, the JAX name stack, e.g.
  ``jit(step_fn)/transpose(jvp(veles.layer.03.conv))/conv_general_
  dilated:`` (a colon and an operation type, often empty, follow).
  It is a stat of the event's METADATA, not of the event, and
  ``jax.profiler.ProfileData`` does not expose those:
  :func:`operation_metadata` walks the protobuf's wire format for it.
  The same metadata holds ``program_id``, the fingerprint in the name
  of the program's ``XLA Modules`` event; an operation belongs to the
  program whose ``XLA Modules`` event holds it in time.
* The names are those the executable was COMPILED with: JAX's default
  compile-cache key leaves metadata out, so a program loaded from a
  cache that an older checkout filled shows that checkout's names (none
  at all in this PR's first look).  ``backends.enable_compilation_
  cache`` now keys the cache on the metadata.
* Under ``value_and_grad`` the forward operations of a scope carry
  ``jvp(<scope>)`` and the backward ones ``transpose(jvp(<scope>))``:
  JAX's own wrapping tells the two apart.
* A Pallas kernel is a custom call named after the ``pallas_call``'s
  ``name=`` (``%veles_attn_decode.7``), and the name is a component of
  its stack: ``.../veles.gpt.attn/veles_attn_decode/pallas_call``.
* What the compiler makes itself (a layout ``copy`` of a parameter, the
  slices and write-backs a ``while`` adds around its body) has no
  ``tf_op``, or one with no ``veles`` name in it (``data:`` on the
  loader's whole-set copy).  A fusion carries the name stack of its
  root instruction, sometimes several joined by ``;``; so the solver's
  update, which XLA fuses into the weight-gradient fusions, shows
  under the backward scope of its layer and ``veles.update`` is left
  with microseconds.

``scope`` below is the chain of the name stack's components that hold
a ``veles`` name, outermost first, joined by ``/``
(``veles.gpt.attn/veles_attn_decode``), so a reader can match an outer
scope or the kernel inside it; ``""`` when there is none.
"""

import os
import re
import sys

from benchmarks import trace_reduce

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)

#: the program's own host spans carry this prefix in the trace
SPAN_PREFIX = "veles:"
UNSCOPED = "unscoped"
_VELES = re.compile(r"veles[._]")


def scope_of(stack):
    """``veles.gpt.attn/veles_attn_decode`` from
    ``jit(decode)/while/body/closed_call/veles.gpt.attn/veles_attn_
    decode/pallas_call``: the components that hold a ``veles`` name,
    wrappers (``transpose(jvp(...))``) kept.  A fusion of operations
    from several scopes carries their name stacks joined by ``;``: the
    first that holds a name counts."""
    for one in stack.split(";"):
        scope = "/".join(part for part in _split_stack(one)
                         if _VELES.search(part))
        if scope:
            return scope
    return ""


def _split_stack(stack):
    """Split at the ``/`` that are not inside brackets (an einsum's
    ``bsd,dchx->bschx`` or a wrapper's argument may hold anything)."""
    parts, depth, start = [], 0, 0
    for index, char in enumerate(stack):
        if char in "([{":
            depth += 1
        elif char in ")]}":
            depth = max(depth - 1, 0)
        elif char == "/" and depth == 0:
            parts.append(stack[start:index])
            start = index + 1
    parts.append(stack[start:])
    return parts


def bare_scope(scope):
    """``veles.layer.03.conv`` from ``transpose(jvp(veles.layer.03.
    conv))``: the innermost component without JAX's wrapping."""
    if not scope:
        return ""
    last = scope.split("/")[-1]
    match = re.search(r"veles[._][A-Za-z0-9_.]*", last)
    return match.group(0) if match else last


# -- the one thing ProfileData does not give: an event's metadata stats -----

def _varint(buf, pos):
    value, shift = 0, 0
    while True:
        byte = buf[pos]
        pos += 1
        value |= (byte & 0x7F) << shift
        if byte < 0x80:
            return value, pos
        shift += 7


def _fields(buf, start=0, end=None):
    """``(field number, wire type, value)`` of one protobuf message:
    a varint's value, or a ``(start, end)`` window of ``buf`` for a
    length-delimited field (nothing is copied, so a whole line of
    events is skipped at the cost of one varint)."""
    pos, end = start, len(buf) if end is None else end
    while pos < end:
        key, pos = _varint(buf, pos)
        number, wire = key >> 3, key & 7
        if wire == 0:
            value, pos = _varint(buf, pos)
        elif wire == 2:
            size, pos = _varint(buf, pos)
            value, pos = (pos, pos + size), pos + size
        elif wire == 1:
            value, pos = None, pos + 8
        elif wire == 5:
            value, pos = None, pos + 4
        else:
            raise ValueError("wire type %d in an .xplane.pb" % wire)
        yield number, wire, value


def _text(buf, window):
    return bytes(buf[window[0]:window[1]]).decode("utf-8", "replace")


def _stat_name(buf, entry):
    """``(id, name)`` of one entry of ``XPlane.stat_metadata``."""
    ident, label = None, ""
    for number, wire, value in _fields(buf, *entry):
        if number == 2 and wire == 2:
            for n, w, v in _fields(buf, *value):
                if n == 1 and w == 0:
                    ident = v
                elif n == 2 and w == 2:
                    label = _text(buf, v)
    return ident, label


def _stat(buf, window, stat_names):
    """``(name, value)`` of one ``XStat``: an integer, a string, or the
    string a ``ref_value`` points at."""
    key, value = None, None
    for number, wire, field in _fields(buf, *window):
        if number == 1 and wire == 0:
            key = field
        elif number in (3, 4) and wire == 0:
            value = field
        elif number == 5 and wire == 2:
            value = _text(buf, field)
        elif number == 7 and wire == 0:
            value = stat_names.get(field, "")
    return stat_names.get(key), value


def _operation(buf, entry, stat_names):
    """``(instruction text, program id, tf_op)`` of one entry of
    ``XPlane.event_metadata``."""
    text, program, stack = "", 0, ""
    for number, wire, value in _fields(buf, *entry):
        if number != 2 or wire != 2:
            continue
        for n, w, v in _fields(buf, *value):        # XEventMetadata
            if n == 2 and w == 2:
                text = _text(buf, v)
            elif n == 5 and w == 2:
                label, stat = _stat(buf, v, stat_names)
                if label == "tf_op" and isinstance(stat, str):
                    stack = stat
                elif label == "program_id" and isinstance(stat, int):
                    program = stat
    return text, program, stack


def operation_metadata(path, plane_prefix="/device:"):
    """``{instruction text: [(program id, tf_op)]}`` of the first
    device plane: the stats ``tf_op`` and ``program_id`` of its
    ``XEventMetadata`` entries, which ``jax.profiler.ProfileData``
    does not expose.  A walk of the protobuf's wire format (``XSpace
    .planes`` = 1; ``XPlane``: name 2, event_metadata 4, stat_metadata
    5; ``XEventMetadata``: name 2, stats 5; ``XStat``: metadata_id 1,
    uint64 3, int64 4, str 5, ref 7), skipping every line of events."""
    with open(path, "rb") as handle:
        buf = memoryview(handle.read())
    for number, wire, plane in _fields(buf):
        if number != 1 or wire != 2:
            continue
        name, entries, stat_names = "", [], {}
        for number, wire, value in _fields(buf, *plane):
            if wire != 2:
                continue
            if number == 2:
                name = _text(buf, value)
            elif number == 4:
                entries.append(value)
            elif number == 5:
                ident, label = _stat_name(buf, value)
                stat_names[ident] = label
        if not name.startswith(plane_prefix) or not entries:
            continue
        out = {}
        for entry in entries:
            text, program, stack = _operation(buf, entry, stat_names)
            if text:
                out.setdefault(text, []).append((program, stack))
        return out
    return {}


def name_stack(tf_op):
    """``tf_op`` is ``<name stack>:<op type>``; the type may be empty."""
    return tf_op.rsplit(":", 1)[0] if ":" in tf_op else tf_op


def extract(path):
    """``{"window": [lo, hi] or None, "spans": [[name, start_ns,
    duration_ns, thread, stats]], "ops": [[program, scope, start_ns,
    duration_ns]], "runs": [[program, start_ns, duration_ns]]}`` of the
    first device plane and the host plane of one ``.xplane.pb``."""
    import jax
    data = jax.profiler.ProfileData.from_file(path)
    metadata = operation_metadata(path)
    spans, window, ops, runs = [], None, [], []
    device_done = False
    for plane in data.planes:
        if plane.name.startswith("/host:"):
            for thread, line in enumerate(plane.lines):
                for event in line.events:
                    name = event.name
                    if name == trace_reduce.WINDOW_SPAN:
                        lo = int(event.start_ns)
                        window = [lo, lo + int(event.duration_ns)]
                    elif name.startswith(SPAN_PREFIX):
                        spans.append([
                            name, int(event.start_ns),
                            int(event.duration_ns), thread,
                            {key: value for key, value in event.stats
                             if isinstance(value, (int, float, str))}])
        elif plane.name.startswith("/device:") and not device_done:
            raw, fingerprints = [], {}
            for line in plane.lines:
                if line.name == trace_reduce.MODULE_LINE:
                    for event in line.events:
                        program = trace_reduce.program_name(event.name)
                        runs.append([program, int(event.start_ns),
                                     int(event.duration_ns)])
                        mark = re.search(r"\((\d+)\)$", event.name)
                        if mark:
                            fingerprints[int(mark.group(1))] = program
                elif line.name == trace_reduce.OPS_LINE:
                    for event in line.events:
                        raw.append([event.name, int(event.start_ns),
                                    int(event.duration_ns)])
            if raw or runs:
                device_done = True
                ops = name_operations(raw, runs, metadata, fingerprints)
    return {"window": window, "spans": spans, "ops": ops, "runs": runs}


def name_operations(raw, runs, metadata, fingerprints):
    """``[program, scope, start, duration]`` of every device operation
    ``[instruction text, start, duration]`` that is no container: the
    program is the one whose run holds the operation's start, the scope
    that of the metadata entry of that program (two programs may hold
    the same instruction text)."""
    import bisect
    ordered = sorted(runs, key=lambda run: run[1])
    starts = [run[1] for run in ordered]
    scopes, out = {}, []
    for text, start, duration in raw:
        index = bisect.bisect_right(starts, start) - 1
        program = ""
        if index >= 0 and start < ordered[index][1] + ordered[index][2]:
            program = ordered[index][0]
        key = (text, program)
        if key not in scopes:
            if trace_reduce.is_container(
                    trace_reduce.short_op_name(text)):
                scopes[key] = None      # its body's events are there
            else:
                entries = metadata.get(text, [])
                mine = [stack for ident, stack in entries
                        if fingerprints.get(ident) == program]
                stacks = mine or [stack for _ident, stack in entries]
                scopes[key] = scope_of(name_stack(stacks[0])) \
                    if stacks else ""
        if scopes[key] is not None:
            out.append([program, scopes[key], start, duration])
    return out


# -- arithmetic on the lists -------------------------------------------------

def window_of(extracted):
    """The ``bench:window`` span, else first to last device event."""
    if extracted.get("window"):
        return tuple(extracted["window"])
    every = [(e[-2], e[-2] + e[-1])
             for e in extracted["ops"] + extracted["runs"]]
    if not every:
        return None
    return min(s for s, _e in every), max(e for _s, e in every)


def whole_runs(extracted, program):
    """``[(start, end)]`` of the program's runs that lie inside the
    window whole, as ``trace_reduce.reduce`` counts them."""
    window = window_of(extracted)
    if window is None:
        return []
    lo, hi = window
    return sorted((start, start + duration)
                  for name, start, duration in extracted["runs"]
                  if name == program and start >= lo
                  and start + duration <= hi)


def program_scopes(extracted, program):
    """``({scope: seconds}, runs)`` of one program: its operations'
    time by scope over the runs that lie in the window whole.  An
    operation with no scope counts as ``unscoped``, except in a
    program all of whose scoped operations carry ONE name (the
    loader's gather): there it counts under that name."""
    import bisect
    runs = whole_runs(extracted, program)
    if not runs:
        return {}, 0
    starts = [start for start, _end in runs]
    totals = {}
    for name, scope, start, duration in extracted["ops"]:
        if name != program:
            continue
        index = bisect.bisect_right(starts, start) - 1
        if index < 0 or start >= runs[index][1]:
            continue            # in a run the window's edge cut
        key = scope or UNSCOPED
        totals[key] = totals.get(key, 0) + duration
    named = {bare_scope(scope) for scope in totals if scope != UNSCOPED}
    if len(named) == 1 and UNSCOPED in totals:
        only = next(scope for scope in totals if scope != UNSCOPED)
        totals[only] += totals.pop(UNSCOPED)
    return {scope: ns / 1e9 for scope, ns in totals.items()}, len(runs)


def matching(totals, match, exclude=None):
    """Sum of the scopes that ``match`` (a regular expression, searched)
    and do not ``exclude``; ``unscoped`` matches only itself."""
    total, found = 0.0, False
    for scope, seconds in totals.items():
        if scope == UNSCOPED:
            hit = match == UNSCOPED
        else:
            hit = match != UNSCOPED and re.search(match, scope) \
                and not (exclude and re.search(exclude, scope))
        if hit:
            total, found = total + seconds, True
    return total if found else None


def programs_carrying(extracted, match):
    """Names of the programs one of whose operations' scopes matches."""
    return sorted({program for program, scope, _s, _d in extracted["ops"]
                   if program and scope and re.search(match, scope)})


def spans_in_window(extracted, name=None, where=None):
    """The spans that lie in the window whole, by name and stats."""
    window = window_of(extracted)
    if window is None:
        return []
    lo, hi = window
    out = []
    for span in extracted["spans"]:
        if name is not None and span[0] != name:
            continue
        if span[1] < lo or span[1] + span[2] > hi:
            continue
        if where and any(span[4].get(key) != value
                         for key, value in where.items()):
            continue
        out.append(span)
    return out


def nested_ns(spans, others):
    """``[ns of each span that others cover]``: for each of ``spans``,
    the union of those of ``others`` that lie inside it on its own
    thread (the span itself left out).  ``others`` are sorted by start
    once a thread, and a span looks only at those that start inside
    it."""
    import bisect
    by_thread = {}
    for other in others:
        by_thread.setdefault(other[3], []).append(other)
    index = {}
    for thread, group in by_thread.items():
        group.sort(key=lambda other: other[1])
        index[thread] = ([other[1] for other in group], group)
    out = []
    for span in spans:
        start, end = span[1], span[1] + span[2]
        starts, group = index.get(span[3], ([], []))
        first = bisect.bisect_left(starts, start)
        last = bisect.bisect_right(starts, end)
        inside = [(o[1], o[1] + o[2]) for o in group[first:last]
                  if o is not span and o[1] + o[2] <= end]
        out.append(sum(e - s for s, e in trace_reduce.merge(inside)))
    return out


def covered(span, others):
    """Nanoseconds of ``span`` that ``others`` cover: the union of
    those among them that lie inside it on its own thread."""
    return nested_ns([span], others)[0]


def self_ns(span, spans):
    """A span's own time: its duration less what the spans nested in
    it on the same thread cover."""
    return span[2] - covered(span, spans)


def span_table(extracted):
    """``{name: (count, total ns, own ns)}`` of the spans that lie in
    the window whole; a span's own time is its duration less what the
    spans nested in it on the same thread cover."""
    spans = spans_in_window(extracted)
    table = {}
    for span, nested in zip(spans, nested_ns(spans, spans)):
        count, total, own = table.get(span[0], (0, 0, 0))
        table[span[0]] = (count + 1, total + span[2],
                          own + span[2] - nested)
    return table


def idle_gaps(extracted, top=10):
    """The device's idle gaps in the window, each labelled by the
    innermost ``veles:`` span that covers at least half of it (the
    shortest such span; failing that, the one that covers most; the
    rule is ``trace_reduce.label_gaps``): ``[[label, seconds]]``,
    largest first."""
    window = window_of(extracted)
    if window is None:
        return []
    lo, hi = window
    busy = trace_reduce.merge(
        (max(start, lo), min(start + duration, hi))
        for _p, _s, start, duration in extracted["ops"]
        if start + duration > lo and start < hi)
    labelled = trace_reduce.label_gaps(
        trace_reduce.idle_between(busy, lo, hi), extracted["spans"],
        SPAN_PREFIX)
    return [[name, ns / 1e9] for name, ns in sorted(
        labelled.items(), key=lambda kv: -kv[1])[:top]]


def report(extracted, out=None):
    """The idle gaps by the program's own spans and the per-scope table
    of each program, as text on ``out`` (standard error)."""
    out = out or sys.stderr
    window = window_of(extracted)
    if window is None:
        return
    print("[program_trace] window %.3f s, %d veles: spans, %d device "
          "operations" % ((window[1] - window[0]) / 1e9,
                          len(extracted["spans"]), len(extracted["ops"])),
          file=out)
    for name, seconds in idle_gaps(extracted):
        print("[program_trace] idle under %-28s %9.3f ms"
              % (name, seconds * 1e3), file=out)
    for name, (count, total, own) in sorted(
            span_table(extracted).items(), key=lambda kv: -kv[1][2]):
        print("[program_trace] span %-32s %6d x %9.3f ms, own %9.3f ms"
              % (name[len(SPAN_PREFIX):], count, total / count / 1e6,
                 own / count / 1e6), file=out)
    for program in sorted({run[0] for run in extracted["runs"]}):
        totals, runs = program_scopes(extracted, program)
        if not runs:
            continue
        whole = sum(end - start
                    for start, end in whole_runs(extracted, program))
        print("[program_trace] %s: %d runs, %.3f ms a run, by scope:"
              % (program, runs, whole / runs / 1e6), file=out)
        for scope, seconds in sorted(totals.items(),
                                     key=lambda kv: -kv[1]):
            print("[program_trace]   %-52s %9.3f ms a run"
                  % (scope, seconds / runs * 1e3), file=out)
    out.flush()


# -- once a process ---------------------------------------------------------

_loaded = {}


def current(view):
    """The extracted lists of this run's trace: ``view["program_trace"]``
    where a test hands them in, else the ``.xplane.pb`` under the
    harness's trace directory, read once a process (and reported on
    standard error then).  ``None`` when the run was not traced."""
    if view.get("program_trace") is not None:
        return view["program_trace"]
    if view.get("trace") is None:
        return None
    if "extracted" not in _loaded:
        try:
            path = trace_reduce.find_xplane(
                os.path.join(ROOT, ".cache", "bench_trace"))
        except FileNotFoundError:
            _loaded["extracted"] = None
        else:
            _loaded["extracted"] = extract(path)
            report(_loaded["extracted"])
    return _loaded["extracted"]
