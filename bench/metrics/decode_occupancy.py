"""decode_occupancy: live slot-steps over (max_slots x live decode steps),
from the window's deltas of the scheduler's `stats()` counters."""


def read(r):
    steps = r.stats.get("decode_steps", 0)
    if steps <= 0:
        return None
    return 100.0 * r.stats["active_slot_steps"] / (r.settings["max_slots"] * steps)
