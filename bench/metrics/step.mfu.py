"""Model FLOPs utilization of the whole RL step over the window: what the
window's work requires (fresh prompts prefilled once per group, decoded
tokens, trained tokens forward and backward; attention included; padding,
remat, re-prefill and over-generation not counted) over
window x chips x peak, counted by the configuration's family
(``counts(c)``). The seconds the profiler takes to write its trace,
between two steps of a traced run, are not the system's and are left out
of the window."""
from bench import roofline


def read(c):
    w = c["w"]
    slot_steps = roofline.decode_tokens(c, traced=False)
    gen = w["engine"].get("generated", 0)
    secs = w["window_s"] - w["trace_stop_s"]
    if secs <= 0 or slot_steps == 0:
        return None
    m = c["family"].counts(c)
    # each decoded token attends the mean live keys of its layer's window
    keys = lambda window: (roofline.decode_key_sum(c, window, traced=False)
                           / slot_steps)
    f = gen * m.forward_token_flops(keys, logits=True)
    # fresh prompts: every token through the stack, logits at the last one
    P, n_prompts = w["prompt_tokens"], w["prompts"]
    mean_p = P / max(1, n_prompts)
    f += P * m.forward_token_flops(lambda window: 0.0, logits=False)
    f += n_prompts * (m.causal_attn_flops(mean_p) + m.head_flops())
    for b in w["batches"]:
        resp = b["loss_mask"].sum(1)
        f += m.train_flops(b["total_lens"], resp)
    peak = c["peaks"]["flops_bf16"] * c["chips"]
    return 100.0 * f / (secs * peak)
