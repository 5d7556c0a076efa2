"""Per step of the program (a span called ``per``), the ms of its spans
called ``span`` — the step itself when ``span`` is ``per`` — less the spans
inside them called by one of the names in ``less`` (a phase's self time is
its span less its children), mean over the steps ``where`` chooses
(``program_spans.where_passes``)."""

from benchmark import program_spans


def read(ctx, span, per="serve_step", less=(), where=None):
    spans = program_spans.of_run(ctx["trace"])
    if spans is None:
        return None
    vals = []
    for step in spans.named(per):
        if not program_spans.where_passes(spans, step, where):
            continue
        secs = 0.0
        for sp in [step] if span == per else spans.inside(span, step):
            secs += sp.end - sp.start
            secs -= sum(c.end - c.start for name in less
                        for c in spans.inside(name, sp))
        vals.append(1e3 * secs)
    if not vals:
        return None
    return sum(vals) / len(vals)
