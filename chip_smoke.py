"""Bring-up check of the CoPRIS trainer on TPU.

    python3 chip_smoke.py                # one chip
    python3 chip_smoke.py --four-chips   # four chips, one host

One chip: each main-path Pallas kernel runs compiled (never interpreted)
against its reference at llama3.2-1b widths; then the trainer that
``repro.launch.train`` builds (``CoPRISTrainer`` -> ``RolloutEngine`` on
the dense cache -> ``make_train_step`` with the fused IS-GRPO loss) takes
three steps at llama3.2-1b's published widths with random weights from
``--seed``, cut in depth only (``LAYERS``).

Four chips: only the disaggregated path — two chips train, two roll out,
the weights resharded between them every version — for three overlapped
steps and one more update with advantages drawn from the seed; each
sharded update is replayed by the one-device train step on the first
device from the same params, optimizer state and batch, and compared.

The last line of standard output is ``{"ok": true, "device": ...}``, and
only when every phase passed. Without a TPU the script exits non-zero
before running anything.
"""
from __future__ import annotations

import argparse
import dataclasses
import gc
import json
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402
import numpy as np  # noqa: E402

# Depth cut, chosen by compiling the train step for a v5e chip on the host
# (memory_analysis of the jitted step at 32 x 320 tokens): 3 layers need
# 10.35 GiB, plus 1.66 GiB for the previous params version the ParamStore
# keeps, plus 1.83 GiB of decode-program temporaries when rollout overlaps
# the update on the same device (the --four-chips baseline): 13.9 GiB of
# 16 GiB. 4 layers come to 15.8 GiB there; donation cannot lower it while
# the ParamStore holds the previous version.
LAYERS = 3
ARCH = "llama3.2-1b"
STEPS = 3
# Kernel against an f32 reference at HIGHEST precision. The kernels feed
# the MXU bf16 operands (the model's compute dtype) and accumulate in f32:
# attention outputs are rounded to bf16 (2^-8 relative), and log-space
# values (logp, entropy) carry about 2^-9 * sqrt(d) * |h| * |w| of logit
# error. Gradients are compared relative to their largest magnitude.
ATOL_BF16 = 2e-2
REL_BF16 = 2e-2


def fail(msg: str):
    raise SystemExit(f"chip_smoke FAILED: {msg}")


def check(cond, msg: str):
    if not cond:
        fail(msg)


class Checks:
    """Collects a phase's failed comparisons, so that one run prints every
    measured error before the phase fails."""

    def __init__(self):
        self.failed = []

    def __call__(self, cond, msg: str):
        if not cond:
            print(f"FAILED: {msg}")
            self.failed.append(msg)

    def done(self, phase: str):
        check(not self.failed, f"{phase}: {'; '.join(self.failed)}")


def max_err(a, b) -> float:
    return float(jnp.max(jnp.abs(jnp.asarray(a, jnp.float32)
                                 - jnp.asarray(b, jnp.float32))))


def rel_err(a, b) -> float:
    b = jnp.asarray(b, jnp.float32)
    return max_err(a, b) / max(float(jnp.max(jnp.abs(b))), 1e-30)


def model_config():
    from repro.configs import get_config
    return dataclasses.replace(get_config(ARCH), num_layers=LAYERS)


# ---------------------------------------------------------------------------
# kernels against their references
# ---------------------------------------------------------------------------


def check_kernels(cfg, seed: int):
    from repro.kernels.decode_attn import ops as da_ops
    from repro.kernels.flash_attn import ops as fa_ops
    from repro.kernels.flash_attn.ref import naive_attention
    from repro.kernels.fused_is_grpo import ops as fio_ops
    from repro.kernels.fused_is_grpo.ref import is_grpo_reference
    from repro.kernels.fused_sample import ops as fs_ops
    from repro.models.attention import decode_attention
    from repro.sampling import sampler

    H, KV, hd, d, V = (cfg.num_heads, cfg.num_kv_heads, cfg.head_dim,
                       cfg.d_model, cfg.vocab_size)
    ok = Checks()
    ks = iter(jax.random.split(jax.random.PRNGKey(seed), 16))
    bf16, f32 = jnp.bfloat16, jnp.float32

    def normal(shape, dtype=f32, scale=1.0):
        return (jax.random.normal(next(ks), shape, f32) * scale).astype(dtype)

    # flash attention (prefill): causal, GQA
    q = normal((8, 256, H, hd), bf16)
    k = normal((8, 256, KV, hd), bf16)
    v = normal((8, 256, KV, hd), bf16)
    got = fa_ops.flash_attention(q, k, v, causal=True, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = naive_attention(q.astype(f32), k.astype(f32), v.astype(f32))
    err = max_err(got, want)
    print(f"kernel flash_attn   B=8 S=256 H={H} KV={KV} hd={hd}: "
          f"max|err|={err}")
    ok(err < ATOL_BF16, f"flash_attn max|err| {err} >= {ATOL_BF16}")

    # decode attention against a dense cache of per-row lengths
    B, L = 48, 320
    q = normal((B, 1, H, hd), bf16)
    kc = normal((B, L, KV * hd), bf16)
    vc = normal((B, L, KV * hd), bf16)
    lens = jax.random.randint(next(ks), (B,), 1, L + 1)
    got = da_ops.decode_attention(q, kc, vc, lens, interpret=False)
    with jax.default_matmul_precision("highest"):
        want = decode_attention(q.astype(f32), kc.astype(f32),
                                vc.astype(f32), lens)
    err = max_err(got, want)
    print(f"kernel decode_attn  B={B} L={L}: max|err|={err}")
    ok(err < ATOL_BF16, f"decode_attn max|err| {err} >= {ATOL_BF16}")

    # fused IS-GRPO loss, forward and gradients, at d and V; a few rows so
    # the unfused (rows, V) reference fits
    rows = (2, 32)
    h = normal(rows + (d,), bf16)
    w = normal((d, V), f32, scale=d ** -0.5)
    tgt = jax.random.randint(next(ks), rows, 0, V)
    beh = -jnp.abs(normal(rows)) - 11.0
    adv = normal(rows)

    def kernel_loss(h, w):
        out = fio_ops.fused_is_grpo(h, w, tgt, beh, adv, impl="pallas",
                                    interpret=False)
        return out[0].sum(), out

    def ref_loss(h, w):
        out = is_grpo_reference(h, w, tgt, beh, adv)
        return out[0].sum(), out

    (_, got), g_got = jax.value_and_grad(kernel_loss, argnums=(0, 1),
                                         has_aux=True)(h, w)
    with jax.default_matmul_precision("highest"):
        (_, want), g_want = jax.value_and_grad(
            ref_loss, argnums=(0, 1), has_aux=True)(h.astype(f32), w)
    # The same reference fed the kernel's MXU operands (bf16 h, and w cast
    # to bf16 by the reference) at default precision: what is left against
    # it is f32 accumulation order. Printed to show where the error against
    # the f32 reference comes from; the bounds below hold against the f32
    # reference.
    (_, want_bf16), g_want_bf16 = jax.value_and_grad(
        ref_loss, argnums=(0, 1), has_aux=True)(h, w)
    names = ("loss_tok", "ratio", "logp", "entropy")
    for name, a, b in zip(names, got, want_bf16):
        print(f"kernel fused_is_grpo fwd {name} vs the bf16-operand "
              f"reference: max|err|={max_err(a, b)}")
    for name, a, b in zip(("dhidden", "dw"), g_got, g_want_bf16):
        print(f"kernel fused_is_grpo bwd {name} vs the bf16-operand "
              f"reference: max|err|/max|ref|={rel_err(a, b)}")
    loss_k, ratio_k, logp_k, ent_k = got
    loss_r, ratio_r, logp_r, ent_r = want
    for name, a, b in (("logp", logp_k, logp_r), ("entropy", ent_k, ent_r)):
        err = max_err(a, b)
        print(f"kernel fused_is_grpo fwd {name} rows={rows} d={d} V={V}: "
              f"max|err|={err}")
        ok(np.isfinite(err) and err < ATOL_BF16,
           f"fused_is_grpo {name} max|err| {err} >= {ATOL_BF16}")
    # ratio = min(exp(logp - behaviour), cap) and loss = -min(ratio*A,
    # clip(ratio)*A): a logp error of at most ATOL_BF16 moves each ratio by
    # at most ratio*(e^ATOL - 1) and each loss by |A| times that. Bound
    # every element by what the logp bound implies (+1e-6 of f32 slack).
    grow = float(np.expm1(ATOL_BF16))
    ratio_bound = ratio_r * grow + 1e-6
    for name, a, b, bound in (
            ("ratio", ratio_k, ratio_r, ratio_bound),
            ("loss_tok", loss_k, loss_r, jnp.abs(adv) * ratio_bound)):
        worst = float(jnp.max(jnp.abs(a - b) / bound))
        print(f"kernel fused_is_grpo fwd {name} rows={rows} d={d} V={V}: "
              f"max|err|={max_err(a, b)}, max|err|/bound={worst} (bound: "
              f"the logp bound {ATOL_BF16} carried through per element)")
        ok(np.isfinite(worst) and worst <= 1.0,
           f"fused_is_grpo {name} exceeds the bound the logp bound implies "
           f"by {worst}x")
    for name, a, b in zip(("dhidden", "dw"), g_got, g_want):
        err = rel_err(a, b)
        print(f"kernel fused_is_grpo bwd {name}: max|err|/max|ref|={err}")
        ok(np.isfinite(err) and err < REL_BF16,
           f"fused_is_grpo {name} max|err|/max|ref| {err} >= {REL_BF16}")

    # fused sampling: the token stream is the XLA sampler's, bit for bit
    keys = jax.random.split(next(ks), B)
    logits = normal((B, V), scale=3.0)
    for temperature, top_p, top_k in ((1.0, 1.0, -1), (1.0, 1.0, 50),
                                      (1.0, 0.9, -1), (0.8, 0.95, 40)):
        kw = dict(temperature=temperature, top_p=top_p, top_k=top_k)
        t_got, lp_got = fs_ops.fused_sample_rows(keys, logits,
                                                 interpret=False, **kw)
        t_want, lp_want = sampler.sample_rows(keys, logits, **kw)
        same = int((np.asarray(t_got) == np.asarray(t_want)).sum())
        err = max_err(lp_got, lp_want)
        print(f"kernel fused_sample {kw} B={B} V={V}: tokens equal "
              f"{same}/{B}, logp max|err|={err}")
        ok(same == B, f"fused_sample {kw}: {B - same} tokens differ")
        ok(err < 1e-3, f"fused_sample {kw}: logp max|err| {err}")
    ok.done("kernels")


# ---------------------------------------------------------------------------
# the trainer, built as repro.launch.train builds it
# ---------------------------------------------------------------------------


def make_trainer(cfg, seed: int, *extra: str):
    from repro.launch import train
    args = train.make_parser().parse_args([
        "--arch", ARCH, "--task", "addition", "--sft-warmup", "0",
        "--batch-size", "8", "--group-size", "4", "--concurrency", "48",
        "--max-response", "256", "--steps", str(STEPS),
        "--seed", str(seed), *extra])
    task = train.make_task(args.task, args.seed)
    return train.build_trainer(args, cfg, task)


def run_steps(tr, label: str, n: int = STEPS):
    """``n`` trainer steps; prints each step's metrics and rollout stats."""
    outs = []
    prev = tr.engine.stats_snapshot()
    for _ in range(n):
        t0 = time.perf_counter()
        out = tr.step()
        wall = time.perf_counter() - t0
        now = tr.engine.stats_snapshot()
        out["evicted"] = now.get("evicted", 0) - prev.get("evicted", 0)
        out["resumed"] = now.get("resumed", 0) - prev.get("resumed", 0)
        prev = now
        outs.append(out)
        print(f"{label} step {out['step']}: wall={wall:.3f}s "
              f"step_time={out['step_time']:.3f}s "
              f"rollout={out['rollout_time']:.3f}s "
              f"update={out['update_time']:.3f}s "
              f"pg_loss={out['pg_loss']} ratio_mean={out['ratio_mean']} "
              f"entropy={out.get('entropy')} "
              f"off_policy_frac={out['off_policy_frac']} "
              f"evicted={out['evicted']} resumed={out['resumed']} "
              f"multi_stage_trajs={out['multi_stage_trajs']} "
              f"mean_resp_len={out['mean_resp_len']} "
              f"param_staleness={out['param_staleness']}")
    return outs


def check_on_policy_start(outs, label: str):
    for o in outs:
        for key in ("pg_loss", "ratio_mean", "entropy", "grad_norm"):
            if key in o:
                check(np.isfinite(o[key]), f"{label}: {key}={o[key]}")
    first = outs[0]
    check(first["off_policy_frac"] == 0.0,
          f"{label}: step 0 off_policy_frac={first['off_policy_frac']}")
    # on-policy: behaviour logps come from the bf16 decode, the recompute
    # from the bf16 full-sequence forward; their per-token gap is bf16
    # rounding, far below this bound on the mean ratio
    check(abs(first["ratio_mean"] - 1.0) < 0.05,
          f"{label}: step 0 ratio_mean={first['ratio_mean']}")


def check_trainer(cfg, seed: int):
    n_params = cfg.param_count()
    print(f"model {ARCH} at published widths (d_model={cfg.d_model}, "
          f"heads={cfg.num_heads}/{cfg.num_kv_heads}, "
          f"head_dim={cfg.head_dim}, d_ff={cfg.d_ff}, "
          f"vocab={cfg.vocab_size}), depth cut 16 -> {LAYERS} layers: "
          f"{n_params} parameters, random from seed {seed}")
    t0 = time.perf_counter()
    tr = make_trainer(cfg, seed)
    print(f"trainer built in {time.perf_counter() - t0:.3f}s "
          f"(rollout use_pallas={tr.engine.use_pallas})")
    check(tr.engine.use_pallas, "the TPU trainer must take the Pallas path")
    try:
        outs = run_steps(tr, "trainer")
    finally:
        tr.close()
    check_on_policy_start(outs, "trainer")
    # concurrency > B*G: the surplus trajectories started under one stage
    # finish under the next and are trained off-policy (off_policy_frac),
    # whether or not any partial was evicted and resumed
    print("trainer carry-over across steps: evicted+resumed="
          f"{sum(o['evicted'] + o['resumed'] for o in outs)}, "
          f"off_policy_frac={[o['off_policy_frac'] for o in outs]}")
    stats = jax.devices()[0].memory_stats() or {}
    print(f"peak_bytes_in_use={stats.get('peak_bytes_in_use')} "
          f"bytes_limit={stats.get('bytes_limit')}")


# ---------------------------------------------------------------------------
# four chips: disaggregated train / rollout against the one-device run
# ---------------------------------------------------------------------------


def _tree_identical(a, b) -> bool:
    return all(np.array_equal(np.asarray(x), np.asarray(y))
               for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))


@jax.jit
def _leaf_update_gap(x0, xa, xb, ma, va, mb, vb, lr, bc1, bc2, eps):
    """One parameter leaf updated by both sides from ``x0``: AdamW takes
    ``x - lr * (u + wd * x0)`` with ``u = m_hat / (sqrt(v_hat) + eps)``
    from each side's own moments, so ``xa - xb`` is ``lr * (ub - ua)`` up
    to f32 rounding of the update: one ulp a side (2^-22 of the larger of
    |x0| and |xa|, a fused or unfused multiply-add), and 1e-4 of the step
    for the f32 sqrt and division. Returns the worst element's gap over
    that allowance (<= 1 passes), sum((ma - mb)^2), sum(mb^2) and
    max|xa - xb|."""
    ua = ma / bc1 / (jnp.sqrt(va / bc2) + eps)
    ub = mb / bc1 / (jnp.sqrt(vb / bc2) + eps)
    gap = jnp.abs(xa - xb - lr * (ub - ua))
    allow = (2.0 ** -22 * jnp.maximum(jnp.abs(x0), jnp.abs(xa))
             + 1e-4 * lr * (jnp.abs(ua) + jnp.abs(ub) + 1.0))
    return (jnp.max(gap / allow), jnp.sum(jnp.square(ma - mb)),
            jnp.sum(jnp.square(mb)), jnp.max(jnp.abs(xa - xb)))


def _compare_update(p0, pa, oa, pb, ob, lr, tcfg, dev):
    """The sharded update (``pa``, ``oa``, on the host) against the
    one-device one (``pb``, ``ob``, on ``dev``) from the same ``p0``,
    leaf by leaf on ``dev``. Returns ||m_a - m_b|| / ||m_b|| (0 when both
    are 0), the worst parameter gap over its f32 allowance, and
    max|pa - pb|."""
    t = float(ob["step"])
    check(int(oa["step"]) == int(ob["step"]), "optimizer step counts differ")
    b1, b2 = tcfg.betas
    consts = [jnp.float32(c) for c in (lr, 1 - b1 ** t, 1 - b2 ** t,
                                       tcfg.eps)]
    gap, num, den, dmax = 0.0, 0.0, 0.0, 0.0
    for leaves in zip(*(jax.tree.leaves(x) for x in (
            p0, pa, pb, oa["m"], oa["v"], ob["m"], ob["v"]))):
        g, n, d, m = _leaf_update_gap(*jax.device_put(leaves, dev), *consts)
        gap, num = max(gap, float(g)), num + float(n)
        den, dmax = den + float(d), max(dmax, float(m))
    m_err = (np.sqrt(num / den) if den > 0.0
             else (0.0 if num == 0.0 else float("inf")))
    return float(m_err), gap, dmax


def check_four_chips(cfg, seed: int):
    """Two chips train (FSDP), two roll out (tensor parallel), overlapped,
    for ``STEPS`` steps. Every sharded update is then replayed by the
    one-device train step from the same params, optimizer state and
    packed batch, and the results compared; the rollout-side copy the
    ParamStore reshards must be the published params bit for bit."""
    from repro.core.copris import make_train_step
    from repro.kernels import pallas_by_default

    ok = Checks()
    tr = make_trainer(cfg, seed, "--overlap", "--disaggregated")
    tr.batch_timeout = 600.0
    train_ids = {d.id for d in tr.train_mesh.devices.flat}
    roll_ids = {d.id for d in tr.rollout_mesh.devices.flat}
    print(f"disaggregated: train devices {sorted(train_ids)}, rollout "
          f"devices {sorted(roll_ids)}")
    check(len(train_ids) == 2 and len(roll_ids) == 2
          and not train_ids & roll_ids, "want 2 + 2 disjoint devices")

    def resharded_check(published, version):
        resharded = tr.param_store.get(version)
        on_roll = all({d.id for d in x.sharding.device_set} <= roll_ids
                      for x in jax.tree.leaves(resharded))
        same = _tree_identical(published, resharded)
        print(f"disaggregated version {version}: resharded params on "
              f"rollout devices={on_roll}, bit-identical to the "
              f"published ones={same}")
        ok(on_roll and same, f"version {version}: resharded params differ "
           "from the published ones or sit off the rollout devices")

    # (label, params before, optimizer state before, device batch, lr,
    #  the sharded step's params, optimizer state and metrics), on the host
    updates = []
    try:
        state = jax.device_get((tr.params, tr.opt_state))
        outs = []
        for _ in range(STEPS):
            stage = tr.stage
            out = run_steps(tr, "disaggregated", 1)[0]
            outs.append(out)
            jb, lr = tr.train_inputs(tr.last_batch, stage)
            after = jax.device_get((tr.params, tr.opt_state))
            updates.append((f"step {stage}", *state, jax.device_get(jb),
                            float(lr), *after, out))
            state = after
            resharded_check(tr.params, tr.stage)
        check_on_policy_start(outs, "disaggregated")
        # A random policy earns no reward, so every advantage above is 0
        # and so is every gradient: those updates are weight decay alone.
        # One more update on the last batch with advantages drawn from the
        # seed gives the sharded gradient something to get wrong.
        tr.close()
        jb = dict(jb, advantages=jax.random.normal(
            jax.random.PRNGKey(seed + 1), jb["advantages"].shape))
        lr = jnp.asarray(tr.tcfg.lr, jnp.float32)
        pa, oa, metrics = tr._train_step(tr.params, tr.opt_state, jb, lr)
        out = {k: float(v) for k, v in metrics.items()}
        updates.append(("seeded advantages", *state, jax.device_get(jb),
                        float(lr), *jax.device_get((pa, oa)), out))
        tr.param_store.publish(pa, tr.stage + 1)
        resharded_check(pa, tr.stage + 1)
        resharded = jax.device_get(tr.param_store.get(tr.stage + 1))
        tcfg = tr.tcfg
    finally:
        tr.close()
    del tr, pa, oa, metrics
    gc.collect()

    # the one-device train step: the path CoPRISTrainer takes on one chip
    dev = jax.devices()[0]
    step = jax.jit(make_train_step(cfg, tcfg, use_pallas=pallas_by_default()))
    for label, p0, o0, jb, lr, pa, oa, out_a in updates:
        pb, ob, mb = step(*jax.device_put((p0, o0, jb), dev),
                          jnp.asarray(lr, jnp.float32))
        out_b = {k: float(v) for k, v in mb.items()}
        # The gradient: first moments after the step. Both sides run the
        # bf16 forward; the sharded side sums bf16 partial gradients across
        # its two devices and takes the XLA loss, the one-device side the
        # fused kernel (logp within 2e-2, the kernel phase's bound). That
        # leaves per-element gradient differences of order 2^-8; a
        # gradient from half the batch, or a stale one, is off by O(1).
        m_err, gap, dmax = _compare_update(p0, pa, oa, pb, ob, lr, tcfg,
                                           dev)
        print(f"four-chip {label}: sharded vs one-device: "
              f"pg_loss {out_a['pg_loss']} vs {out_b['pg_loss']}, "
              f"ratio_mean {out_a['ratio_mean']} vs {out_b['ratio_mean']}, "
              f"entropy {out_a['entropy']} vs {out_b['entropy']}, "
              f"grad_norm {out_a['grad_norm']} vs {out_b['grad_norm']}, "
              f"||m_a - m_b||/||m_b||={m_err}, params: worst gap / f32 "
              f"allowance={gap}, max|p_a - p_b|={dmax}")
        ok(m_err <= 5e-2, f"{label}: first moments differ by {m_err}")
        ok(gap <= 1.0, f"{label}: params differ by more than their "
           f"moments imply ({gap}x the f32 allowance)")
        # Token means of per-token values whose roundings differ
        # independently: 1e-2 of the mean, and 1e-4 absolute for the
        # pg_loss of zero advantages (exactly 0 on both sides).
        for key in ("pg_loss", "ratio_mean", "entropy", "grad_norm"):
            ok(np.allclose(out_a[key], out_b[key], rtol=1e-2, atol=1e-4),
               f"{label}: {key} {out_a[key]} vs {out_b[key]}")
    # the rollout side's copy of the last update against the one-device
    # update: identical to the sharded params, so off by their gap alone
    gap = max(float(jnp.max(jnp.abs(jax.device_put(r, dev) - b)))
              for r, b in zip(jax.tree.leaves(resharded),
                              jax.tree.leaves(pb)))
    print(f"four-chip rollout copy vs one-device params: max|err|={gap}")
    ok.done("four chips")


# ---------------------------------------------------------------------------


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chips", action="store_true",
                    help="run only the disaggregated four-chip phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    print(f"platform={device['platform']} device_kind={device['kind']} "
          f"count={device['count']}")
    if device["platform"] != "tpu":
        print("chip_smoke: no TPU visible; nothing was run", file=sys.stderr)
        return 1
    need = 4 if args.four_chips else 1
    if device["count"] < need:
        print(f"chip_smoke: needs {need} TPU devices, found "
              f"{device['count']}", file=sys.stderr)
        return 1

    from repro.common.compile_cache import enable_compile_cache
    print(f"compile cache: {enable_compile_cache()}")
    cfg = model_config()
    phases = ([("four chips", check_four_chips)] if args.four_chips else
              [("kernels", check_kernels), ("trainer", check_trainer)])
    t0 = time.perf_counter()
    failed = []
    for name, phase in phases:
        # a failed phase is reported and the next one still runs, so one
        # run shows every phase; any failure ends in a non-zero exit
        try:
            phase(cfg, args.seed)
            print(f"phase {name} passed at {time.perf_counter() - t0:.3f}s")
        except (Exception, SystemExit) as e:
            traceback.print_exc()
            print(f"phase {name} FAILED: {e}")
            failed.append(name)
    if failed:
        print(f"chip_smoke FAILED: {', '.join(failed)}", file=sys.stderr)
        return 1
    print(f"all phases passed in {time.perf_counter() - t0:.3f}s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
