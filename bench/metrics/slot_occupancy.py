"""slot_occupancy: engine counters over the window, active slot-steps over
all slot-steps of the decode steps (%)."""


def read(ctx):
    c = ctx.counters
    if not c["total_slot_steps"]:
        return None
    return 100.0 * c["active_slot_steps"] / c["total_slot_steps"]
