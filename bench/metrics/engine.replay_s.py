"""Seconds a window step spends replaying decode chunks on the host, per
step: the program's ``engine.replay`` span (appending tokens, stop checks,
dispatching refills; no device work), summed into ``replay_time``."""


def read(c):
    steps = c["w"]["steps"]
    if not steps or any("replay_time" not in s for s in steps):
        return None
    return sum(s["replay_time"] for s in steps) / len(steps)
