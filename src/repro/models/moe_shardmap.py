"""Expert-parallel MoE dispatch via shard_map + explicit all_to_all.

The auto-SPMD scatter dispatch (apply_moe_sparse) replicates its token
buffers (EXPERIMENTS.md §Perf D); this module implements the production
pattern instead: experts live sharded on the "model" axis, each device
routes its local tokens, exchanges them with one `jax.lax.all_to_all`,
runs its local experts, and reverses the exchange.

Capacity is per (source device, expert): tokens beyond it are dropped
(residual passthrough), exactly like the capacity dispatcher. Opt-in via
``MoEConfig.dispatch = "shardmap"`` (requires an active mesh with a
"model" axis); validated against the dense oracle in
tests/test_moe_shardmap.py on an 8-device host mesh.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P


def apply_moe_shardmap(params, cfg, x, mesh, *, capacity_factor=None):
    """x: (B, S, d) batch-sharded over the data axes. Returns (y, aux)."""
    m = cfg.moe
    E = m.num_experts
    ep = mesh.shape["model"]
    assert E % ep == 0, (E, ep)
    e_local = E // ep
    d = cfg.d_model
    B, S, _ = x.shape
    cf = capacity_factor if capacity_factor is not None else m.capacity_factor
    dp = tuple(a for a in ("pod", "data") if a in mesh.axis_names)

    def local(xt, router, wi, wg, wo, shared):
        """Per-device: xt (T_local, d) tokens; router (d, E) replicated;
        wi/wg (e_local, d, f); wo (e_local, f, d)."""
        T = xt.shape[0]
        dt = xt.dtype
        # per-(device, expert) capacity
        cap = max(1, int(cf * T * m.top_k / E))

        logits = xt.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        top_w, top_i = jax.lax.top_k(probs, m.top_k)
        top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
        onehot = jax.nn.one_hot(top_i, E, dtype=jnp.float32)
        aux = E * jnp.sum(onehot.sum(1).mean(0) * probs.mean(0))

        # slot assignment within each expert's local queue
        flat_e = top_i.reshape(-1)                       # (T*k,)
        pos_in_e = jnp.cumsum(jax.nn.one_hot(flat_e, E, dtype=jnp.int32),
                              axis=0)
        pos = (jnp.take_along_axis(pos_in_e, flat_e[:, None], 1)
               .squeeze(-1) - 1)
        keep = pos < cap
        slot = jnp.where(keep, flat_e * cap + pos, E * cap)

        # sendbuf[e*cap + c] = token routed to expert e, slot c
        sendbuf = jnp.zeros((E * cap + 1, d), dt)
        tok_idx = jnp.repeat(jnp.arange(T), m.top_k)
        sendbuf = sendbuf.at[slot].set(xt[tok_idx])
        send = sendbuf[: E * cap].reshape(ep, e_local * cap, d)

        # exchange: device p receives every device's tokens for ITS experts
        recv = jax.lax.all_to_all(send, "model", split_axis=0, concat_axis=0,
                                  tiled=False)            # (ep, e_local*cap, d)
        xe = (recv.reshape(ep, e_local, cap, d)
              .transpose(1, 0, 2, 3)
              .reshape(e_local, ep * cap, d))             # per local expert

        h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xe, wg.astype(dt))) \
            * jnp.einsum("ecd,edf->ecf", xe, wi.astype(dt))
        ye = jnp.einsum("ecf,efd->ecd", h, wo.astype(dt))  # (e_local, ep*cap, d)

        back = (ye.reshape(e_local, ep, cap, d)
                .transpose(1, 0, 2, 3)
                .reshape(ep, e_local * cap, d))
        got = jax.lax.all_to_all(back, "model", split_axis=0, concat_axis=0,
                                 tiled=False).reshape(E * cap, d)
        got = jnp.concatenate([got, jnp.zeros((1, d), dt)], axis=0)

        flat_w = jnp.where(keep, top_w.reshape(-1), 0.0)
        y = jnp.zeros((T, d), dt)
        y = y.at[tok_idx].add(got[slot] * flat_w[:, None].astype(dt)
                              * keep[:, None].astype(dt))
        return y, aux[None]

    def local_nosh(xt, router, wi, wg, wo):
        return local(xt, router, wi, wg, wo, None)

    # tokens flattened and sharded over the FULL device grid — every device
    # routes DISTINCT tokens (with x replicated over "model", all ranks
    # routed identical copies and each expert processed its tokens ep times:
    # measured 8.5x compute blowup) and any (batch, mesh) divisibility works
    grid = dp + ("model",)
    n_dev = 1
    for a in grid:
        n_dev *= mesh.shape[a]
    T_all = B * S
    pT = (-T_all) % n_dev
    xt_all = x.reshape(T_all, d)
    if pT:
        xt_all = jnp.pad(xt_all, ((0, pT), (0, 0)))

    fn = jax.shard_map(
        local_nosh, mesh=mesh,
        in_specs=(P(grid, None), P(), P("model", None, None),
                  P("model", None, None), P("model", None, None)),
        out_specs=(P(grid, None), P(dp or None)),
        check_vma=False)
    y, aux = fn(xt_all, params["router"], params["wi"], params["wg"],
                params["wo"])
    y = y[:T_all].reshape(B, S, d)
    if "shared" in params:             # shared experts are dense — no EP
        sp = params["shared"]
        dt = x.dtype
        hs = jax.nn.silu(x @ sp["wg"].astype(dt)) * (x @ sp["wi"].astype(dt))
        y = y + hs @ sp["wo"].astype(dt)
    return y, aux.mean()
