"""A percentile of one of the request's own times as the ENGINE took
them (`response.timings[key]`, ms; `queue_ms` is submit to admission),
over the requests whose first token came before the traced slice
began (the whole window where the run traced none: see
iteration_record). A request in that set that the engine never
admitted lies beyond the percentile. None where the program's
responses carry no timings."""
from benchmark import serve_window
from benchmark.readers.iteration_record import slice_start


def read(ctx, key, q=90):
    cut = slice_start(ctx)
    values = []
    for r in ctx["sent"]:
        timings = getattr(r.response, "timings", None)
        if timings is None:
            return None
        if cut is None or (r.stamps and r.stamps[0] < cut):
            values.append(timings.get(key, float("inf")))
    return serve_window.percentile(values, q) if values else None
