"""Host milliseconds per dispatched batch in a drain: the fleet's batch
wall time (input build, dispatch, sync, collect; compiles excluded)
less the time the host waited on the device, over the batches of the
traced window.  From the fleet's registry (program counters)."""


def read(ctx):
    r = ctx["registry"]
    if not r["batches"]:
        return None
    return 1e3 * (r["wall_s"] - r["sync_s"]) / r["batches"]
