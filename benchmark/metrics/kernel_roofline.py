"""Kernels: the least time the chip could take for a query's bytes
(each column it reads, once, at its staged width, times the table's
rows; HBM-bound) over the device-busy time a query took in the traced
window: ALL device-op time there over the queries' worth of work done
in it, whatever kernels did it. So it is also the whole query's share of
the chip's peak: a kernel taken off the path cannot hide from it."""
import peaks


def read(ctx):
    if ctx["rehearsal"] or not ctx["busy_s"] or not ctx["queries_in_trace"]:
        return None
    templates = ctx["mix"]["templates"]  # round-robin: equal shares
    nbytes = sum(peaks.bytes_per_query(ctx["config"], ctx["cardinalities"],
                                       t, ctx["rows"])
                 for t in templates) / len(templates)
    least, _bound = peaks.least_seconds(ctx["device_kind"], nbytes)
    return 100.0 * least / (ctx["busy_s"] / ctx["queries_in_trace"])
