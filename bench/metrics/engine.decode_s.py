"""Seconds a window step spends in decode chunks, per step: the program's
``engine.decode`` span, from the chunk's dispatch to its tokens on the
host, so the device's decode time included (``decode_time``)."""


def read(c):
    steps = c["w"]["steps"]
    if not steps or any("decode_time" not in s for s in steps):
        return None
    return sum(s["decode_time"] for s in steps) / len(steps)
