"""Decode attention's share of its roofline over the traced steps: the
least time the chip could take for the live keys the decoded tokens attend
(the larger of FLOPs over peak and bytes over HBM bandwidth, counted by
the configuration's family) over the kernel's device time in the trace.
Bytes count each live slot's own keys and values only, at most a layer's
window of them, which is what the algorithm needs."""
from bench import roofline

# the Pallas call takes the name of the jitted wrapper it is traced in
KERNEL = r"decode_chunk/decode_attention$"


def read(c):
    t = c["trace"]
    secs = t.time_of(KERNEL) if t is not None else 0.0
    tokens = roofline.decode_tokens(c, traced=True)
    if secs <= 0 or tokens == 0:
        return None
    keys = lambda window: roofline.decode_key_sum(c, window, traced=True)
    flops, byts = c["family"].counts(c).decode_attn_cost(keys, tokens)
    p = c["peaks"]
    least = max(flops / p["flops_bf16"], byts / p["hbm_bytes_s"])
    return 100.0 * least / secs
