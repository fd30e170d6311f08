"""Plain float32 reference of the ``qwen2`` family's dense decoder
(``bench/families/qwen2.py``), with the RL update the trainer applies
(GRPO with cross-stage importance sampling, token-mean, global-norm clip,
AdamW).

Written from the published description (Qwen2 / Qwen3 blocks: RMSNorm,
rotary positions on the two halves of each head, grouped-query causal
attention, optional per-head q/k RMSNorm, SwiGLU MLP, final RMSNorm, tied
or untied head) and from the paper's loss. It imports nothing of the system
under test. Departure, shared with the system: no q/k/v bias.

Every matrix product runs at ``precision="highest"`` on float32; with
``precision="fp8"`` each product's operands are instead scaled per tensor
and rounded to float8 e4m3 (the control: the nearest precision below the
configurations' bfloat16). Rows go through in blocks, so the reference fits
beside nothing else at the timed sizes.
"""
from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
import numpy as np

HI = jax.lax.Precision.HIGHEST
F8 = jnp.float8_e4m3fn
F8_MAX = 448.0


def _fake8(a):
    """``a`` rounded to float8 e4m3 under a per-tensor scale, in float32."""
    s = jnp.maximum(jnp.max(jnp.abs(a)), 1e-30) / F8_MAX
    return (a / s).astype(F8).astype(jnp.float32) * s


def _ein(spec, a, b):
    return jnp.einsum(spec, a, b, precision=HI,
                      preferred_element_type=jnp.float32)


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm8(spec, a, b):
    return _ein(spec, _fake8(a), _fake8(b))


def _mm8_fwd(spec, a, b):
    a8, b8 = _fake8(a), _fake8(b)
    return _ein(spec, a8, b8), (a8, b8)


def _mm8_bwd(spec, res, g):
    # the backward products take float8 operands too, the cotangent
    # under its own per-tensor scale
    a8, b8 = res
    _, vjp = jax.vjp(functools.partial(_ein, spec), a8, b8)
    return vjp(_fake8(g))


_mm8.defvjp(_mm8_fwd, _mm8_bwd)


def mm(spec, a, b, precision):
    if precision == "fp8":
        return _mm8(spec, a, b)
    return _ein(spec, a, b)


def rms(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * w


def rope(x, pos, theta):
    hd = x.shape[-1]
    inv = 1.0 / theta ** (np.arange(0, hd, 2, dtype=np.float64) / hd)
    ang = pos[:, None].astype(jnp.float32) * jnp.asarray(inv, jnp.float32)
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    a, b = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([a * cos - b * sin, b * cos + a * sin], -1)


def logprobs(params, c, tokens, precision):
    """log p(tokens[:, t+1] | tokens[:, :t+1]) for t < S-1: (n, S-1)."""
    n, S = tokens.shape
    H, KV, hd, eps = c["heads"], c["kv_heads"], c["head_dim"], c["eps"]
    pos = jnp.arange(S)
    x = params["embed"]["tok"][tokens].astype(jnp.float32)
    body = params["stack"]["body"][0]
    causal = pos[None, :] <= pos[:, None]

    def layer(x, p):
        h = rms(x, p["ln1"], eps)
        q = mm("nsd,de->nse", h, p["attn"]["wq"], precision)
        k = mm("nsd,de->nse", h, p["attn"]["wk"], precision)
        v = mm("nsd,de->nse", h, p["attn"]["wv"], precision)
        q = q.reshape(n, S, H, hd)
        k = k.reshape(n, S, KV, hd)
        v = v.reshape(n, S, KV, hd)
        if "q_norm" in p["attn"]:
            q = rms(q, p["attn"]["q_norm"], eps)
            k = rms(k, p["attn"]["k_norm"], eps)
        q, k = rope(q, pos, c["theta"]), rope(k, pos, c["theta"])
        k = jnp.repeat(k, H // KV, axis=2)     # head i reads kv group i // R
        v = jnp.repeat(v, H // KV, axis=2)
        s = mm("nqhd,nkhd->nhqk", q, k, precision) / math.sqrt(hd)
        s = jnp.where(causal, s, -jnp.inf)
        a = mm("nhqk,nkhd->nqhd", jax.nn.softmax(s, -1), v, precision)
        x = x + mm("nse,ed->nsd", a.reshape(n, S, H * hd), p["attn"]["wo"],
                   precision)
        h = rms(x, p["ln2"], eps)
        g = mm("nsd,df->nsf", h, p["mlp"]["wg"], precision)
        u = mm("nsd,df->nsf", h, p["mlp"]["wi"], precision)
        x = x + mm("nsf,fd->nsd", jax.nn.silu(g) * u, p["mlp"]["wo"],
                   precision)
        return x, None

    x, _ = jax.lax.scan(jax.checkpoint(layer), x, body)
    h = rms(x[:, :-1], params["final_norm"], eps)
    w = params["embed"]["tok"].T if c["tied"] else params["lm_head"]
    logits = mm("nsd,dv->nsv", h, w, precision)
    lp = jax.nn.log_softmax(logits, -1)
    return jnp.take_along_axis(lp, tokens[:, 1:, None], -1)[..., 0]


def static_of(cfg: dict) -> tuple:
    return tuple(sorted(dict(
        heads=cfg["num_attention_heads"], kv_heads=cfg["num_key_value_heads"],
        head_dim=cfg["head_dim"], eps=cfg["rms_norm_eps"],
        theta=float(cfg["rope_theta"]),
        tied=bool(cfg["tie_word_embeddings"])).items()))


@functools.partial(jax.jit, static_argnums=(0, 1))
def _logp_block(cs, precision, params, tokens):
    return logprobs(params, dict(cs), tokens, precision)


@functools.partial(jax.jit, static_argnums=(0, 1, 2))
def _loss_block(cs, precision, hyper, params, tokens, mask, beh, fresh, adv,
                denom):
    """Σ(loss_tok · mask) / denom over a block of rows, and the new log-probs.
    ``fresh`` marks tokens sampled by these very params: their behaviour is
    the new log-prob itself (ratio 1 to rounding)."""
    clip_low, clip_high, cap = hyper

    def f(params):
        lp = logprobs(params, dict(cs), tokens, precision)
        b = jnp.where(fresh, jax.lax.stop_gradient(lp), beh)
        lr = jnp.clip(lp - b, -math.log(cap), math.log(cap))
        r = jnp.exp(lr)
        a = adv[:, None]
        obj = jnp.minimum(r * a, jnp.clip(r, 1 - clip_low, 1 + clip_high) * a)
        return jnp.sum(-obj * mask) / denom, lp

    (loss, lp), g = jax.value_and_grad(f, has_aux=True)(params)
    return loss, lp, g


def group_advantages(rewards, G):
    r = np.asarray(rewards, np.float64).reshape(-1, G)
    return ((r - r.mean(1, keepdims=True))
            / (r.std(1, keepdims=True) + 1e-6)).reshape(-1).astype(np.float32)


SCORES_BUDGET = 1 << 30   # bytes of a block's attention scores
LOGITS_BUDGET = 3 << 30   # bytes of a block's float32 logits


def block_rows(cfg: dict, T: int) -> int:
    """Rows per block, a power of two: a block's attention scores within
    ``SCORES_BUDGET`` and its float32 logits within ``LOGITS_BUDGET``."""
    per_row = cfg["num_attention_heads"] * T * T * 4
    n = max(1, min(SCORES_BUDGET // per_row,
                   LOGITS_BUDGET // (T * cfg["vocab_size"] * 4)))
    return 1 << (n.bit_length() - 1)


def _leaf_norms(tree):
    return [float(jnp.linalg.norm(x.astype(jnp.float32).ravel()))
            for x in jax.tree.leaves(tree)]


def run(cfg: dict, hyper: dict, params0, batches, reward_fn, *,
        precision: str = "highest", make_params=None):
    """Follow the trainer through ``len(batches)`` steps from ``params0``.

    ``batches[k]``: the packed batch the trainer trained at step k (tokens,
    loss_mask, stage_ids, prompt_lens, total_lens). Rewards are recomputed
    here from the response tokens; the behaviour log-prob of every token is
    this reference's own, under its own params of the stage that sampled it.

    Returns losses per step (with the mean |advantage| over the loss's
    tokens, the scale a loss gap is read in), the global norm of the first
    gradient before clipping and the per-leaf norms of the clipped one, the per-leaf norms of the params' change over all steps, and
    for each batch the reference log-prob of every sampled token under the
    params that sampled it (NaN where no token)."""
    cs = static_of(cfg)
    hy = (hyper["clip_low"], hyper["clip_high"], hyper["is_ratio_cap"])
    G = hyper["group_size"]
    params = params0
    m = jax.tree.map(jnp.zeros_like, params)
    v = jax.tree.map(jnp.zeros_like, params)
    K = len(batches)
    beh = [np.zeros(b["tokens"][:, 1:].shape, np.float32) for b in batches]
    ref_lp = [np.full(b["tokens"][:, 1:].shape, np.nan, np.float32)
              for b in batches]
    losses, g0_norms, adv_scale = [], None, []
    for k in range(K):
        # behaviour of stage-k tokens in later batches, under params_k
        for j in range(k + 1, K):
            b = batches[j]
            st = b["stage_ids"][:, 1:]
            rows = np.nonzero(((st == k) & (b["loss_mask"][:, 1:] > 0))
                              .any(1))[0]
            for r in rows:
                lp = np.asarray(_logp_block(cs, precision, params,
                                            jnp.asarray(b["tokens"][r:r + 1])))
                sel = st[r] == k
                beh[j][r, sel] = lp[0, sel]
                ref_lp[j][r, sel] = lp[0, sel]
        b = batches[k]
        tokens = b["tokens"]
        N, T = tokens.shape
        mask = b["loss_mask"][:, 1:].astype(np.float32)
        fresh = b["stage_ids"][:, 1:] == k
        rewards = [reward_fn(tokens[n, b["prompt_lens"][n]:b["total_lens"][n]])
                   for n in range(N)]
        adv = group_advantages(rewards, G)
        adv_scale.append(float((np.abs(adv)[:, None] * mask).sum()
                               / max(mask.sum(), 1.0)))
        # the trainer's gradient accumulation: the loss is the mean over
        # microbatches (contiguous row groups) of each one's token mean
        mbs = hyper.get("microbatches", 1)
        per = N // mbs
        nb = min(block_rows(cfg, T), per)
        loss = 0.0
        grads = jax.tree.map(jnp.zeros_like, params)
        for r0 in range(0, N, nb):
            sl = slice(r0, r0 + nb)
            mb = slice(r0 // per * per, r0 // per * per + per)
            denom = np.float32(max(mask[mb].sum(), 1.0) * mbs)
            l, lp, g = _loss_block(cs, precision, hy, params,
                                   jnp.asarray(tokens[sl]),
                                   jnp.asarray(mask[sl]),
                                   jnp.asarray(beh[k][sl]),
                                   jnp.asarray(fresh[sl]),
                                   jnp.asarray(adv[sl]), denom)
            loss += float(l)
            grads = jax.tree.map(jnp.add, grads, g)
            lp = np.asarray(lp)
            f = fresh[sl]
            ref_lp[k][sl][f] = lp[f]
        losses.append(loss)
        gn = math.sqrt(sum(float(jnp.sum(x * x))
                           for x in jax.tree.leaves(grads)))
        scale = min(1.0, hyper["grad_clip"] / max(gn, 1e-9))
        grads = jax.tree.map(lambda x: x * scale, grads)
        if k == 0:
            g0_norms, grad_norm0 = _leaf_norms(grads), gn
        params, m, v = _adam(params, m, v, grads, k, hyper)
    p0 = params0 if make_params is None else make_params()
    delta = _leaf_norms(jax.tree.map(jnp.subtract, params, p0))
    return dict(losses=losses, g0_norms=g0_norms, grad_norm0=grad_norm0,
                delta_norms=delta, ref_logp=ref_lp, adv_scale=adv_scale,
                params=params)


def lr_at(step: int, hyper: dict) -> float:
    return hyper["lr"] * min(1.0, (step + 1) / max(hyper["warmup_steps"], 1))


@jax.jit
def _adam_step(params, m, v, grads, lr, bc1, bc2, b1, b2, eps, wd):
    m = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, m, grads)
    v = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, v, grads)
    params = jax.tree.map(
        lambda p, m, v: p - lr * ((m / bc1) / (jnp.sqrt(v / bc2) + eps)
                                  + wd * p), params, m, v)
    return params, m, v


def _adam(params, m, v, grads, k, hyper):
    b1, b2 = hyper["betas"]
    t = k + 1
    return _adam_step(params, m, v, grads, lr_at(k, hyper), 1 - b1 ** t,
                      1 - b2 ** t, b1, b2, hyper["adam_eps"],
                      hyper["weight_decay"])
