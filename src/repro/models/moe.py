"""Mixture-of-Experts FFN — two dispatch mechanisms, one routing contract.

1. ``moe_ffn_einsum`` — GShard-style dense one-hot dispatch
   (``[B,S,E,C]`` einsums).  Simple and exact, but its dispatch FLOPs are
   O(T * E * C * D): at deepseek-v3 scale that is ~8x the model's useful
   compute.  Kept as the small-scale reference path (CPU tests, smoke
   configs) and as the oracle the sorted path is tested against.

2. ``moe_ffn_sorted`` / ``moe_ffn_ep`` — **sort-based dispatch**: tokens
   argsort by expert id, segment-rank gives each token its capacity slot,
   one scatter builds the per-expert batch, experts run as a vmapped
   matmul, one gather+scatter-add combines.  This is the Indexed
   DataFrame's shuffle (hash -> stable sort -> segment rank -> scatter,
   dist/shuffle.py) applied to expert routing — the paper's routing
   substrate and the MoE dispatch are literally the same algorithm
   (DESIGN.md §3).  Dispatch cost falls to sort + O(T*k*D) memory moves.

   ``moe_ffn_ep`` wraps the sorted dispatch in ``shard_map`` for expert
   parallelism: experts shard over the ``model`` axis; each shard packs
   only tokens routed to *its* experts (routing math is replicated over
   the model axis, so no metadata exchange is needed), and the combine is
   one ``psum`` over the model axis — the same collective class as a
   Megatron-TP FFN all-reduce.

Flavors covered:
  * shared experts (deepseek v2/v3: always-on experts added to routed out)
  * softmax top-k routing (classic) and sigmoid scoring (deepseek-v3)
  * aux-loss-free balancing bias (ds-v3) + standard load-balance aux loss
  * first-k-dense layers (ds v2/v3), MoE-every-k layers (jamba)
"""

from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.common import ModelConfig, MoEConfig, dense_init, swiglu
from repro.models import sharding as shd
from repro.models.sharding import hint

# dtype of the EP combine psum (§Perf lever: bf16 halves the collective
# bytes of the per-layer [B,S,D] all-reduce; None = f32 exact)
COMBINE_DTYPE = None


def moe_init(key, cfg: ModelConfig, dtype):
    m = cfg.moe
    ks = jax.random.split(key, 8)
    d, f, e = cfg.d_model, m.d_ff_expert, m.num_experts
    p = {
        "router": dense_init(ks[0], d, e, jnp.float32),
        "w_gate": (jax.random.normal(ks[1], (e, d, f), jnp.float32)
                   / jnp.sqrt(d)).astype(dtype),
        "w_up": (jax.random.normal(ks[2], (e, d, f), jnp.float32)
                 / jnp.sqrt(d)).astype(dtype),
        "w_down": (jax.random.normal(ks[3], (e, f, d), jnp.float32)
                   / jnp.sqrt(f)).astype(dtype),
    }
    if m.router_aux_free_bias:
        p["router_bias"] = jnp.zeros((e,), jnp.float32)
    if m.num_shared:
        fs = f * m.num_shared
        p["shared_gate"] = dense_init(ks[4], d, fs, dtype)
        p["shared_up"] = dense_init(ks[5], d, fs, dtype)
        p["shared_down"] = dense_init(ks[6], fs, d, dtype)
    return p


def _capacity(tokens_per_group: int, m: MoEConfig) -> int:
    c = int(tokens_per_group * m.top_k / m.num_experts * m.capacity_factor)
    return max(4, -(-c // 4) * 4)


def route(p, x, m: MoEConfig):
    """Top-k routing.  Returns (weights [B,S,K], experts [B,S,K], aux)."""
    logits = jnp.einsum("bsd,de->bse", x.astype(jnp.float32), p["router"])
    if m.router == "sigmoid":                     # deepseek-v3
        scores = jax.nn.sigmoid(logits)
        sel = scores + (p["router_bias"] if m.router_aux_free_bias else 0.0)
    else:
        scores = jax.nn.softmax(logits, axis=-1)
        sel = scores
    _, topk_idx = jax.lax.top_k(sel, m.top_k)                 # [B,S,K]
    topk_w = jnp.take_along_axis(scores, topk_idx, axis=-1)
    if m.router == "sigmoid":
        topk_w = topk_w / jnp.maximum(
            topk_w.sum(-1, keepdims=True), 1e-9)
    # load-balance aux loss (Switch-style)
    probs = scores if m.router == "softmax" else \
        scores / jnp.maximum(scores.sum(-1, keepdims=True), 1e-9)
    density = jnp.mean(jax.nn.one_hot(topk_idx, m.num_experts,
                                      dtype=jnp.float32), axis=(0, 1, 2))
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = m.num_experts * jnp.sum(density * mean_prob)
    return topk_w, topk_idx, aux


def moe_ffn(p, x, cfg: ModelConfig):
    """[B, S, D] -> ([B, S, D], aux_loss).  Capacity-dropped tokens pass
    through (residual semantics).

    Mechanism selection: expert-parallel sorted dispatch when a mesh with
    a 'model' axis that divides num_experts is active (production path);
    dense einsum otherwise (reference path).
    """
    mesh = shd._mesh()
    rules = shd._rules()
    if mesh is not None and rules is not None:
        model_axis = rules.get("experts")
        if model_axis is not None and isinstance(model_axis, str):
            esz = mesh.shape[model_axis]
            if cfg.moe.num_experts % esz == 0:
                return moe_ffn_ep(p, x, cfg, mesh=mesh,
                                  dp=rules.get("batch"),
                                  model_axis=model_axis)
    return moe_ffn_einsum(p, x, cfg)


def moe_ffn_einsum(p, x, cfg: ModelConfig):
    """Dense one-hot dispatch (reference / small-scale path)."""
    m = cfg.moe
    b, s, d = x.shape
    cap = _capacity(s, m)
    w, idx, aux = route(p, x, m)

    onehot = jax.nn.one_hot(idx, m.num_experts, dtype=jnp.float32)  # [B,S,K,E]
    # position of each (token, k) within its expert queue
    pos = jnp.cumsum(onehot.reshape(b, s * m.top_k, m.num_experts),
                     axis=1) - 1.0
    pos = pos.reshape(b, s, m.top_k, m.num_experts)
    keep = (pos < cap) & (onehot > 0)
    pos_oh = jax.nn.one_hot(jnp.where(keep, pos, cap).astype(jnp.int32),
                            cap, dtype=jnp.float32)          # [B,S,K,E,C]
    dispatch = (onehot[..., None] * pos_oh).sum(2)            # [B,S,E,C]
    combine = (w[..., None, None] * onehot[..., None]
               * pos_oh).sum(2)                               # [B,S,E,C]
    dispatch = hint(dispatch, "batch", "seq", "experts", "expert_cap")

    xin = jnp.einsum("bsec,bsd->ebcd", dispatch,
                     x.astype(jnp.float32)).astype(cfg.jnp_dtype)
    xin = hint(xin, "experts", "batch", "expert_cap", "model_d")
    h = jax.vmap(lambda xi, g, u, dn: swiglu(xi, g, u, dn))(
        xin, p["w_gate"], p["w_up"], p["w_down"])             # [E,B,C,D]
    h = hint(h, "experts", "batch", "expert_cap", "model_d")
    out = jnp.einsum("bsec,ebcd->bsd", combine,
                     h.astype(jnp.float32)).astype(cfg.jnp_dtype)

    if m.num_shared:
        out = out + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return hint(out, "batch", "res_seq", "model_d"), aux


# ---------------------------------------------------------------------------
# Sort-based dispatch (the shuffle algorithm applied to expert routing)
# ---------------------------------------------------------------------------

def _segment_rank(sorted_ids):
    n = sorted_ids.shape[0]
    pos = jnp.arange(n, dtype=jnp.int32)
    is_start = jnp.concatenate(
        [jnp.ones((1,), bool), sorted_ids[1:] != sorted_ids[:-1]])
    start = jax.lax.associative_scan(jnp.maximum,
                                     jnp.where(is_start, pos, -1))
    return pos - start


def _dispatch_sorted(x_flat, idx, wts, wg, wu, wd, cap: int, e_lo,
                     n_local: int, out_dtype):
    """Route [T,D] tokens to ``n_local`` experts starting at ``e_lo``.

    x_flat [T,D]; idx [T,K] expert ids; wts [T,K] combine weights;
    wg/wu/wd [n_local, ...] expert weights.  Returns [T,D] contribution of
    these experts (zeros for tokens routed elsewhere/dropped).
    """
    t, k = idx.shape
    d = x_flat.shape[1]
    tk = t * k
    eid = idx.reshape(tk)
    w_flat = wts.reshape(tk).astype(jnp.float32)
    tok = jnp.arange(tk, dtype=jnp.int32) // k

    order = jnp.argsort(eid, stable=True)          # shuffle's stable sort
    eid_s, tok_s, w_s = eid[order], tok[order], w_flat[order]
    rank = _segment_rank(eid_s)                    # capacity slot per token

    local = (eid_s >= e_lo) & (eid_s < e_lo + n_local)
    keep = local & (rank < cap)
    slot = jnp.where(keep, (eid_s - e_lo) * cap + rank,
                     jnp.int32(n_local * cap))     # OOB = drop

    buf = jnp.zeros((n_local * cap, d), out_dtype)
    buf = buf.at[slot].set(x_flat[tok_s].astype(out_dtype), mode="drop")
    h = jax.vmap(swiglu)(buf.reshape(n_local, cap, d), wg, wu, wd)
    h_flat = h.reshape(n_local * cap, d)

    vals = h_flat[jnp.minimum(slot, n_local * cap - 1)].astype(jnp.float32)
    vals = vals * (keep[:, None] * w_s[:, None])
    out = jnp.zeros((t, d), jnp.float32)
    out = out.at[tok_s].add(vals)
    return out


def moe_ffn_sorted(p, x, cfg: ModelConfig):
    """Single-device sorted dispatch (tested against moe_ffn_einsum)."""
    m = cfg.moe
    b, s, d = x.shape
    cap = _capacity(b * s, m)
    w, idx, aux = route(p, x, m)
    out = _dispatch_sorted(x.reshape(b * s, d), idx.reshape(b * s, m.top_k),
                           w.reshape(b * s, m.top_k), p["w_gate"],
                           p["w_up"], p["w_down"], cap, jnp.int32(0),
                           m.num_experts, cfg.jnp_dtype)
    out = out.reshape(b, s, d).astype(cfg.jnp_dtype)
    if m.num_shared:
        out = out + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return out, aux


def moe_ffn_ep(p, x, cfg: ModelConfig, *, mesh, dp, model_axis: str):
    """Expert-parallel sorted dispatch under shard_map.

    Experts shard over ``model_axis``; activations shard over ``dp``
    (batch).  Routing is computed inside the shard_map block — x does not
    vary over the model axis, so every model shard derives identical
    routing without any metadata exchange.  Each shard packs + computes
    its local experts; the combine is one psum over the model axis.
    """
    m = cfg.moe
    b, s, d = x.shape
    esz = mesh.shape[model_axis]
    n_local = m.num_experts // esz

    def local_fn(xl, router, bias, wg, wu, wd):
        bl = xl.shape[0]
        t = bl * s
        cap = _capacity(t, m)
        # routing (replicated over model axis)
        pl = {"router": router}
        if bias is not None:
            pl["router_bias"] = bias
        w, idx, aux = route(pl, xl, m)
        j = jax.lax.axis_index(model_axis)
        e_lo = (j * n_local).astype(jnp.int32)
        out = _dispatch_sorted(xl.reshape(t, d), idx.reshape(t, m.top_k),
                               w.reshape(t, m.top_k), wg, wu, wd, cap,
                               e_lo, n_local, cfg.jnp_dtype)
        if COMBINE_DTYPE is not None:
            out = out.astype(COMBINE_DTYPE)
        out = jax.lax.psum(out.reshape(bl, s, d), model_axis)
        aux = jax.lax.pmean(aux, model_axis)
        return out.astype(cfg.jnp_dtype), aux

    xspec = P(dp, None, None)
    espec = P(model_axis, None, None)
    bias = p.get("router_bias")
    out, aux = jax.shard_map(
        local_fn, mesh=mesh,
        in_specs=(xspec, P(None, None), None if bias is None else P(None),
                  espec, espec, espec),
        out_specs=(xspec, P()), check_vma=False,
    )(x, p["router"], bias, p["w_gate"], p["w_up"], p["w_down"])

    if m.num_shared:
        out = out + swiglu(x, p["shared_gate"], p["shared_up"],
                           p["shared_down"])
    return hint(out, "batch", "res_seq", "model_d"), aux
