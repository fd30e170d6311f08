"""Model FLOPs utilization of the whole RL step over the window: what the
window's work requires (fresh prompts prefilled once per group, decoded
tokens, trained tokens forward and backward; attention included; padding,
remat, re-prefill and over-generation not counted) over
window x chips x peak. The seconds the profiler takes to write its trace,
between two steps of a traced run, are not the system's and are left out
of the window."""
from bench import roofline


def read(c):
    cfg, w = c["cfg"], c["w"]
    ctx_sum, slot_steps = c["decode"]
    gen = w["engine"].get("generated", 0)
    secs = w["window_s"] - w["trace_stop_s"]
    if secs <= 0 or slot_steps == 0:
        return None
    mean_ctx = ctx_sum / slot_steps
    f = gen * roofline.forward_token_flops(cfg, mean_ctx, logits=True)
    # fresh prompts: every token through the stack, logits at the last one
    P, n_prompts = w["prompt_tokens"], w["prompts"]
    mean_p = P / max(1, n_prompts)
    f += P * roofline.forward_token_flops(cfg, 0.0, logits=False)
    f += n_prompts * (roofline.attn_flops(cfg, 1.0)
                      * roofline.causal_ctx_sum(mean_p)
                      + 2.0 * roofline.head_params(cfg))
    for b in w["batches"]:
        resp = b["loss_mask"].sum(1)
        f += roofline.train_flops(cfg, b["total_lens"], resp)
    peak = c["peaks"]["flops_bf16"] * c["chips"]
    return 100.0 * f / (secs * peak)
