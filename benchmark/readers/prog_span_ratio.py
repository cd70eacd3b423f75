"""One stat of the program's spans named ``span`` over another, percent, both
summed over the spans inside the traced window: ``of`` ``real_tokens``
``over`` ``padded_tokens`` of ``engine.prefill.batch`` is the share of an
admission batch's computed tokens that were prompt."""

from ..lib import program_trace


def read(ctx, params):
    pt = program_trace.of(ctx)
    if pt is None:
        return None
    spans = pt.named(params["span"])
    over = sum(float(s.stats.get(params["over"], 0)) for s in spans)
    if not over:
        return None
    of = sum(float(s.stats.get(params["of"], 0)) for s in spans)
    return 100.0 * of / over
