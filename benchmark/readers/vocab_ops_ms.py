"""Self seconds of the device operations that work over rows of the whole
vocabulary, per event of ``per_span``, in ms: ``what`` = ``head`` (the matrix
product that makes the logits: the operation that reads the ``[hidden,
vocabulary]`` weights, whatever XLA fused into it) or ``sample`` (everything else that reads or
writes ``[rows, vocabulary]``: the arg-max, the softmax's sums, the reveal
rule of a model that generates by blocks — what stands between the head and
the ``[rows, B]`` integers that cross the link).

A device operation's event is named by its instruction's whole text, operand
shapes and all; the reduced trace keeps the short name alone, so this reader
goes back to the file (found as ``program_spans`` finds it) and takes the
operations whose text holds an array with the vocabulary as its last
dimension (the embedding table has it first).  Control-flow operations, which
contain other events, are left out.  No such operation in the window is no
reading."""

from benchmark import program_spans, trace_reduce

_CONTAINERS = (" while(", " conditional(", " call(")


def read(ctx, what, per_span):
    tr = ctx["trace"]
    n = len(tr.span_list(per_span))
    path = program_spans.newest_trace()
    desc = ctx["result"].get("desc", {})
    vocab = desc.get("vocab_size")
    if not n or path is None or not vocab or not tr.devices():
        return None
    from jax.profiler import ProfileData

    mark = f",{vocab}]"
    # the head's product is the operation that reads the head's weights
    weights = f"[{desc.get('hidden_size')},{vocab}]"
    lo, hi = tr.window()
    secs, devices = 0.0, 0
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(trace_reduce.DEVICE_PLANE_PREFIX):
            continue
        devices += 1
        for line in plane.lines:
            if line.name != trace_reduce.OPS_LINE:
                continue
            for ev in line.events:
                text = ev.name
                start = ev.start_ns * 1e-9
                end = start + ev.duration_ns * 1e-9
                if (mark not in text or start < lo or end > hi
                        or any(c in text for c in _CONTAINERS)):
                    continue
                if (weights in text) == (what == "head"):
                    secs += end - start
    if secs == 0.0 or not devices:
        return None
    return 1e3 * secs / devices / n
