"""Share of decode slot-steps that advanced a live trajectory, from the
engine's counters over the window (``active_slot_steps / slot_steps``)."""


def read(c):
    e = c["w"]["engine"]
    if not e.get("slot_steps"):
        return None
    return 100.0 * e["active_slot_steps"] / e["slot_steps"]
