"""Feed-forward mixers: SwiGLU MLP and MoE.

MoE dispatch has two implementations:

* ``gather`` (baseline): pjit-global sort-based dispatch. Tokens are routed
  into an (E, C, d) buffer with scatter/gather; GSPMD inserts the collectives.
* ``shardmap`` (optimized): activations replicated over the `model` axis,
  experts sharded over `model`; each model-rank dispatches locally into its
  own expert shard and the combine is a single psum — no all-to-all, no
  global gather of the token array. See EXPERIMENTS.md §Perf.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models import common as cm


# ---------------------------------------------------------------------------
# Dense SwiGLU
# ---------------------------------------------------------------------------

def swiglu_init(key, d_model, d_ff):
    ks = jax.random.split(key, 3)
    return {
        "gate": cm.dense(ks[0], d_model, d_ff, ("embed", "mlp")),
        "up": cm.dense(ks[1], d_model, d_ff, ("embed", "mlp")),
        "down": cm.dense(ks[2], d_ff, d_model, ("mlp", "embed")),
    }


def swiglu(p, x):
    from repro.distributed import sharding as shd
    # 'seq' (not None) in the hidden constrain: under sequence-parallel
    # prefill the activation stays seq-sharded — a None here would force a
    # full-sequence gather AND replicate the up-projection compute.
    axes = ("batch",) + ("seq",) * (x.ndim - 2) + ("mlp",)
    g = shd.constrain(cm.apply_dense(p["gate"], x), axes)
    u = cm.apply_dense(p["up"], x)
    return cm.apply_dense(p["down"], jax.nn.silu(g) * u)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_init(key, cfg):
    moe = cfg.moe
    ks = jax.random.split(key, 5)
    d, ff, e = cfg.d_model, cfg.d_ff, moe.num_experts
    def expert_w(k, d_in, d_out, axes):
        w = jax.random.truncated_normal(k, -2., 2., (e, d_in, d_out)) * (
            1.0 / jnp.sqrt(d_in))
        return {"w": cm.Param(w, ("expert",) + axes)}
    p = {
        "router": cm.dense(ks[0], d, e, ("embed", "expert")),
        "gate": expert_w(ks[1], d, ff, ("embed", "mlp")),
        "up": expert_w(ks[2], d, ff, ("embed", "mlp")),
        "down": expert_w(ks[3], ff, d, ("mlp", "embed")),
    }
    if moe.shared_expert_ff:
        p["shared"] = swiglu_init(ks[4], d, moe.shared_expert_ff)
    return p


def _route(router_p, x2d, moe):
    """x2d: (T, d) -> (weights (T,k), experts (T,k))."""
    logits = cm.apply_dense(router_p, x2d).astype(jnp.float32)   # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, experts = jax.lax.top_k(probs, moe.top_k)
    weights = weights / jnp.maximum(
        jnp.sum(weights, axis=-1, keepdims=True), 1e-9)
    return weights, experts


def _capacity(n_tokens, moe):
    c = int(n_tokens * moe.top_k / moe.num_experts * moe.capacity_factor)
    return max(8, -(-c // 8) * 8)  # round up to 8


def _dispatch_compute_combine(p, x2d, weights, experts, capacity, moe):
    """Sort-based dispatch -> grouped expert SwiGLU -> weighted combine.

    x2d (T,d); weights/experts (T,k). Returns (T,d).
    """
    t, d = x2d.shape
    e, k = moe.num_experts, moe.top_k
    n = t * k
    flat_e = experts.reshape(n)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.bincount(flat_e, length=e)
    starts = jnp.cumsum(counts) - counts
    pos = jnp.arange(n) - starts[sorted_e]           # slot within expert block
    keep = pos < capacity
    dest_c = jnp.where(keep, pos, capacity)          # overflow -> col `capacity`
    tok = order // k

    slot_tok = jnp.full((e, capacity + 1), t, jnp.int32)
    slot_tok = slot_tok.at[sorted_e, dest_c].set(tok, mode="drop")
    slot_w = jnp.zeros((e, capacity + 1), weights.dtype)
    slot_w = slot_w.at[sorted_e, dest_c].set(weights.reshape(n)[order],
                                             mode="drop")
    slot_tok, slot_w = slot_tok[:, :capacity], slot_w[:, :capacity]

    x_pad = jnp.concatenate([x2d, jnp.zeros((1, d), x2d.dtype)], axis=0)
    xs = x_pad[slot_tok]                             # (E, C, d)

    def _w(q):
        return q["w"].value if cm.is_param(q["w"]) else q["w"]
    wg = _w(p["gate"]).astype(xs.dtype)
    wu = _w(p["up"]).astype(xs.dtype)
    wd = _w(p["down"]).astype(xs.dtype)
    if wg.shape[0] < e:  # shard_map local path: drop the phantom expert row
        xs, slot_tok, slot_w = (a[:wg.shape[0]] for a in (xs, slot_tok, slot_w))
    h = jax.nn.silu(jnp.einsum("ecd,edf->ecf", xs, wg)) * jnp.einsum(
        "ecd,edf->ecf", xs, wu)
    out = jnp.einsum("ecf,efd->ecd", h, wd)          # (E, C, d)

    out = out * slot_w[..., None].astype(out.dtype)
    y = jnp.zeros((t + 1, d), out.dtype).at[slot_tok.reshape(-1)].add(
        out.reshape(-1, d), mode="drop")
    return y[:t]


def moe_forward_gather(p, x, cfg):
    """Baseline pjit-global MoE. x: (B, S, d)."""
    moe = cfg.moe
    b, s, d = x.shape
    x2d = x.reshape(b * s, d)
    weights, experts = _route(p["router"], x2d, moe)
    cap = _capacity(b * s, moe)
    y = _dispatch_compute_combine(p, x2d, weights, experts, cap, moe)
    if "shared" in p:
        y = y + swiglu(p["shared"], x2d)
    return y.reshape(b, s, d)


def moe_forward_shardmap(p, x, cfg, mesh, *, dp_axes=("data",),
                         ep_axis="model"):
    """Optimized MoE: local dispatch per (data, model) block + psum combine.

    Token activations are replicated over `model`; expert weights are sharded
    over `model`. Each model-rank routes its (replicated) token block against
    the full router, dispatches only the tokens destined for ITS experts, and
    contributes a partial output; a single psum over `model` combines.
    """
    moe = cfg.moe
    e_total = moe.num_experts
    ep = mesh.shape[ep_axis]
    e_local = e_total // ep
    assert e_local * ep == e_total, (e_total, ep)

    def local_fn(x_blk, router_w, wg, wu, wd, shared):
        b, s, d = x_blk.shape
        x2d = x_blk.reshape(b * s, d)
        weights, experts = _route({"w": router_w}, x2d, moe)
        my = jax.lax.axis_index(ep_axis)
        lo = my * e_local
        # keep only (token, k) choices routed to my expert shard
        mine = (experts >= lo) & (experts < lo + e_local)
        local_experts = jnp.where(mine, experts - lo, e_local)  # e_local = drop
        local_weights = jnp.where(mine, weights, 0.0)
        cap = max(8, _capacity(b * s, moe) // ep * 2)  # local capacity w/ slack
        lp = {"gate": {"w": wg}, "up": {"w": wu}, "down": {"w": wd}}
        lmoe = _LocalMoE(e_local, moe.top_k)
        y = _dispatch_compute_combine(lp, x2d, local_weights, local_experts,
                                      cap, lmoe)
        y = jax.lax.psum(y, ep_axis)
        if shared is not None:
            y = y + swiglu(shared, x2d)
        return y.reshape(b, s, d)

    x_spec = P(dp_axes, None, None)
    shared = p.get("shared")
    fn = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(x_spec, P(None, None), P(ep_axis, None, None),
                  P(ep_axis, None, None), P(ep_axis, None, None),
                  None if shared is None else jax.tree.map(
                      lambda _: P(None, None), cm.values(shared))),
        out_specs=x_spec, check_vma=False)
    return fn(x, p["router"]["w"].value,
              p["gate"]["w"].value, p["up"]["w"].value, p["down"]["w"].value,
              None if shared is None else cm.values(shared))


class _LocalMoE:
    """Duck-typed stand-in for MoEConfig inside the shard_map local block:
    one extra phantom expert id (= e_local) absorbs dropped tokens."""
    def __init__(self, e_local, top_k):
        self.num_experts = e_local + 1
        self.top_k = top_k
