"""Seconds a window step spends packing its batch, per step: the program's
``trainer.pack`` span (``pack_groups``, the train inputs and their copy to
the device), summed into ``pack_time``."""


def read(c):
    steps = c["w"]["steps"]
    if not steps or any("pack_time" not in s for s in steps):
        return None
    return sum(s["pack_time"] for s in steps) / len(steps)
