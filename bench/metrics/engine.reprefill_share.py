"""Share of the window's prefilled tokens that were prefilled again: the
prompt and response so far of every partial resumed by re-prefill
(``reprefill_tokens``) over every token prefilled (``prefill_tokens``),
from the engine's counters over the window."""


def read(c):
    e = c["w"]["engine"]
    if "reprefill_tokens" not in e or not e.get("prefill_tokens"):
        return None
    return 100.0 * e["reprefill_tokens"] / e["prefill_tokens"]
