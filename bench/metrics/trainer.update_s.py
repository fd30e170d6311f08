"""Seconds a window step spends in the update (``update_time``: packing,
the train step to ``block_until_ready``, publish), per step."""


def read(c):
    steps = c["w"]["steps"]
    return sum(s["update_time"] for s in steps) / len(steps)
