"""Share of a cohort's batch slots that carry a request: dispatched
jobs over dispatches times the batch size, in the traced window, from
the service's counters."""


def read(ctx):
    r = ctx["registry"]
    if not r["dispatches"]:
        return None
    return 100.0 * r["dispatched_jobs"] / (r["dispatches"]
                                           * ctx["batch_size"])
