"""Share of the traced window in which no operation runs on the device and
the host is in the admission path outside a prefill's own span: between the
logits of one prefill and the dispatch of the next (the first token's host
sample, the seat, the next request's pages, slot and feed), or around a
poll. Those are the idle instants whose innermost span is `decode.admit_ms`:
the engine opens one such span for each request's part before its prefill
and one for its seat (`part=`, `rid=`) inside the loop's own, so that a
fill's gaps, whose one long `decode.admit_ms` span straddles the traced
window and is not in the trace, are under a span too. None on a program
that has no such parts (no `decode.seat_ms` histogram: the parent commit),
whose fill would read as idle under no span."""

from benchmark.readers._idle_split import share
from benchmark.readers._telemetry import hist


def read(ctx):
    if hist(ctx, "decode.seat_ms", "count") is None:
        return None
    return share(ctx, "serve", "decode.admit_ms")
