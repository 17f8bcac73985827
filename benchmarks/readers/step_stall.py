"""What the scheduler's steps ran before their decode, from the stats
``prefills`` (the prefill and chunk dispatches before the decode) and
``decoded`` (the tokens the decode emitted) of the ``veles:gen/step``
spans that lie in the traced window whole.  args: ``mode``:

- ``share``: 100 x the tokens decoded by steps with at least
  ``min_prefills`` prefills over the tokens decoded by all steps: the
  share of token gaps that waited behind that many prefills;
- ``stall_ms``: over the steps with a prefill, the mean host time nested
  in the step, on its thread, under ``veles:gen/admit`` and
  ``veles:gen/prefill_chunk``: how long such a decode waited.

None when untraced, or where the steps carry no such stats (a program
that does not count them)."""

from benchmarks import program_trace

STEP = "veles:gen/step"
STALLS = ("veles:gen/admit", "veles:gen/prefill_chunk")


def _counted(span):
    return all(isinstance(span[4].get(key), int)
               for key in ("prefills", "decoded"))


def read(view):
    extracted = program_trace.current(view)
    if extracted is None:
        return None
    steps = [span for span in program_trace.spans_in_window(
        extracted, STEP) if _counted(span)]
    args = view["args"]
    if args["mode"] == "share":
        total = sum(span[4]["decoded"] for span in steps)
        if not total:
            return None
        behind = sum(span[4]["decoded"] for span in steps
                     if span[4]["prefills"] >= args["min_prefills"])
        return 100.0 * behind / total
    stalled = [span for span in steps if span[4]["prefills"] >= 1]
    if not stalled:
        return None
    inner = [span for span in extracted["spans"] if span[0] in STALLS]
    return sum(program_trace.nested_ns(stalled, inner)) \
        / len(stalled) / 1e6
