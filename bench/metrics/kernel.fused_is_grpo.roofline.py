"""The fused IS+GRPO loss kernels' share of their roofline over the traced
steps, forward and backward together: least time for the operations and
bytes their shapes require (``roofline.fused_is_grpo_cost``) over the
kernels' device time in the trace. The rows required are the positions
whose token the loss scores (the ``loss_mask`` of the traced batches), not
the prompt or the padding that the kernel also runs over."""
from bench import roofline

# under the custom VJP the forward kernel is named ``jvp__`` and the two
# backward kernels (dh, dw) ``transpose_jvp___`` in the compiled step
KERNELS = r"train_step/(transpose_)?jvp_+$"


def read(c):
    t = c["trace"]
    secs = t.time_of(KERNELS) if t is not None else 0.0
    w = c["w"]
    rows = sum(float(b["loss_mask"].sum())
               for b in w["batches"][:w["traced_steps"]])
    if secs <= 0 or rows == 0:
        return None
    flops, byts = roofline.fused_is_grpo_cost(c["cfg"], rows)
    p = c["peaks"]
    least = max(flops / p["flops_bf16"], byts / p["hbm_bytes_s"])
    return 100.0 * least / secs
