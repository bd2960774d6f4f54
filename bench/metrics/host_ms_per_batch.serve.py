"""Host milliseconds per dispatched batch in the service: the fleet's
batch wall time less the device wait, over the batches of the traced
window, read from the service's registry (program counters)."""


def read(ctx):
    r = ctx["registry"]
    if not r["batches"]:
        return None
    return 1e3 * (r["wall_s"] - r["sync_s"]) / r["batches"]
