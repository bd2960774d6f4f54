"""Device-busy nanoseconds, summed over the chips used, per simulated
lane-step offered (vector retires times runtime threads, from the
jobs' event counters) in the traced window: what one unit of simulated
work costs the device, whichever tier ran it."""


def read(ctx):
    t, steps = ctx["trace"], ctx["lane_steps"]
    if t is None or not steps:
        return None
    return 1e9 * sum(t["busy_s_per_device"]) / steps
