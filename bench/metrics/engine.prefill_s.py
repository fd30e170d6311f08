"""Seconds a window step spends in the engine's batched prefills, per step:
the program's ``engine.prefill`` span (the stage-start fill and every
refill, through their ``device_get``), summed into ``prefill_time``."""


def read(c):
    steps = c["w"]["steps"]
    if not steps or any("prefill_time" not in s for s in steps):
        return None
    return sum(s["prefill_time"] for s in steps) / len(steps)
