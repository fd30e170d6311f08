"""Seconds a window step spends collecting its rollout stage
(``rollout_time``, the engine's forced wall time), per step."""


def read(c):
    steps = c["w"]["steps"]
    return sum(s["rollout_time"] for s in steps) / len(steps)
