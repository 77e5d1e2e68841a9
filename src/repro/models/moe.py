"""Mixture-of-Experts: fine-grained experts, shared experts, top-k routing.

Dispatch uses the permute/capacity formulation (the same sort + segment-rank
dataflow as the GRNND request router in core/pools.py — one framework, one
idiom): token->expert assignments are sorted by expert, capacity-capped,
scattered into an (E*C, D) buffer, batched through the expert FFNs with one
(E, C, D) x (E, D, F) einsum pair, and combined back with routing weights.
Tokens over capacity are dropped (standard capacity-factor semantics).

Under pjit the expert axis shards over "model" (EP); the scatter/gather
between token-space (data-sharded) and expert-space (model-sharded) lowers
to all-to-all-style collectives.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models import layers as L


def init_moe_params(key, cfg: ArchConfig, dtype=jnp.float32):
    d, e, de = cfg.d_model, cfg.n_experts, cfg.d_expert
    ks = L.split_keys(key, 7)
    p = {
        "router": L.dense_init(ks[0], (d, e), dtype=jnp.float32),  # fp32 router
        "wi_gate": L.dense_init(ks[1], (e, d, de), in_axis=1, dtype=dtype),
        "wi_up": L.dense_init(ks[2], (e, d, de), in_axis=1, dtype=dtype),
        "wo": L.dense_init(ks[3], (e, de, d), in_axis=1, dtype=dtype),
    }
    if cfg.n_shared_experts:
        f = cfg.n_shared_experts * de
        p["shared"] = {
            "wi_gate": L.dense_init(ks[4], (d, f), dtype=dtype),
            "wi_up": L.dense_init(ks[5], (d, f), dtype=dtype),
            "wo": L.dense_init(ks[6], (f, d), dtype=dtype),
        }
    return p


def _capacity(cfg: ArchConfig, t: int) -> int:
    c = int(cfg.moe_capacity_factor * t * cfg.top_k / cfg.n_experts) + 1
    return max(8, -(-c // 8) * 8)  # round up to a lane-friendly multiple


def _permute_ffn(params, cfg: ArchConfig, xt, probs, w, idx, *,
                 e_local: int, e_offset, wi_gate, wi_up, wo):
    """Dispatch/compute/combine for `e_local` experts starting at e_offset.

    xt (T, D); w/idx (T, k) routing weights and expert ids (global ids).
    Returns the weighted sum of local-expert outputs per token (T, D) —
    the caller psums over the expert-parallel axis if e_local < E.
    """
    t, d = xt.shape
    k = cfg.top_k

    flat_e = idx.reshape(t * k) - e_offset
    in_range = (flat_e >= 0) & (flat_e < e_local)
    flat_e = jnp.where(in_range, flat_e, e_local)          # OOB bucket
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)

    order = jnp.argsort(flat_e, stable=True)
    es = flat_e[order]
    toks = tok[order]
    pos_in = jnp.arange(t * k, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.array([True]), es[1:] != es[:-1]])
    seg0 = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos_in, 0))
    rank = pos_in - seg0

    c = _capacity(cfg, t)
    kept = (rank < c) & (es < e_local)
    slot = jnp.where(kept, es * c + rank, e_local * c)

    # Invert the permutation with SMALL integer scatters only: big-tensor
    # scatters lower to full-width index broadcasts (8 GiB of u32 per op at
    # this scale); with the inverse map both dispatch and combine become
    # gathers, which partition and fuse cleanly.
    row_of_slot = jnp.zeros((e_local * c,), jnp.int32) \
        .at[slot].set(toks, mode="drop")                      # (E_loc*C,)
    slot_valid = jnp.zeros((e_local * c,), jnp.bool_) \
        .at[slot].set(kept, mode="drop")
    slot_by_assign = jnp.full((t * k,), e_local * c, jnp.int32) \
        .at[order].set(jnp.where(kept, slot, e_local * c))    # (T*k,)

    buf = xt[row_of_slot] * slot_valid[:, None].astype(xt.dtype)

    h = buf.reshape(e_local, c, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h, wi_gate.astype(xt.dtype)))
    u = jnp.einsum("ecd,edf->ecf", h, wi_up.astype(xt.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", g * u,
                       wo.astype(xt.dtype)).reshape(e_local * c, d)

    # combine: gather each assignment's expert output, weight, sum over k
    sl = slot_by_assign.reshape(t, k)
    ok = sl < e_local * c
    picked = out_e[jnp.where(ok, sl, 0)]                      # (T, k, D)
    wk = jnp.where(ok, w, 0.0).astype(xt.dtype)
    y = jnp.einsum("tkd,tk->td", picked, wk)
    drop_frac = 1.0 - jnp.sum(kept.astype(jnp.float32)) / \
        jnp.maximum(jnp.sum(in_range.astype(jnp.float32)), 1.0)
    return y, drop_frac


def _moe_block_ep(params, cfg: ArchConfig, x: jnp.ndarray, hints):
    """Expert-parallel MoE via shard_map: tokens sharded over the data
    axes, experts over the model axis.  Dispatch is a LOCAL select (tokens
    are replicated across the model axis), combine is ONE psum of the
    (T_local, D) partial output — the cheapest EP dataflow for capacity-
    based routing, and the same owner-routing idiom as the GRNND
    distributed build (DESIGN.md §4.3).
    """
    from jax.sharding import PartitionSpec as PSpec


    b, s, d = x.shape
    e = cfg.n_experts
    m_ax = hints.model_axis
    n_ep = hints.mesh.shape[m_ax]
    assert e % n_ep == 0
    e_loc = e // n_ep

    tspec = PSpec(hints.data_axes, None)       # tokens over data axes
    espec = PSpec(m_ax)                        # experts over model

    def body(xt, router, wi_gate, wi_up, wo):
        ridx = jax.lax.axis_index(m_ax)
        e0 = ridx * e_loc
        # router matmul in activation dtype: an fp32 (T, D) input would
        # materialize an 8 GiB fp32 tensor + its VJP per layer; fp32
        # precision is only needed on the tiny (T, E) logits.
        logits = (xt @ router.astype(xt.dtype)).astype(jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        w, idx = jax.lax.top_k(probs, cfg.top_k)
        w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)
        y_part, drop = _permute_ffn(
            params, cfg, xt, probs, w, idx, e_local=e_loc, e_offset=e0,
            wi_gate=wi_gate, wi_up=wi_up, wo=wo)
        y = jax.lax.psum(y_part, m_ax)
        # load-balance stats via bincount scatter (no (T, k, E) one-hot)
        counts = jnp.zeros((e,), jnp.float32).at[idx.reshape(-1)].add(1.0)
        me = counts / jnp.maximum(jnp.sum(counts), 1.0)
        pe = jnp.mean(probs, axis=0)
        lb = e * jnp.sum(me * pe)
        return y, lb, jax.lax.pmean(drop, m_ax)

    xt = x.reshape(b * s, d)
    y, lb, drop = jax.shard_map(
        body, mesh=hints.mesh,
        in_specs=(tspec, PSpec(), espec, espec, espec),
        out_specs=(tspec, PSpec(), PSpec()),
        check_vma=False,
    )(xt, params["router"], params["wi_gate"], params["wi_up"],
      params["wo"])

    if cfg.n_shared_experts:
        sp = params["shared"]
        y = y + L.gated_mlp(xt, sp["wi_gate"], sp["wi_up"], sp["wo"])
    aux = {"moe_lb_loss": lb, "moe_drop_frac": drop}
    return y.reshape(b, s, d), aux


def moe_block(params, cfg: ArchConfig, x: jnp.ndarray):
    """x (B, S, D) -> (out (B, S, D), aux metrics dict)."""
    from repro.distributed import hints as H
    hints = H.get_hints()
    if hints is not None and hints.model_axis is not None \
            and cfg.n_experts % hints.mesh.shape[hints.model_axis] == 0:
        return _moe_block_ep(params, cfg, x, hints)

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    t = b * s
    xt = x.reshape(t, d)

    logits = (xt.astype(jnp.float32) @ params["router"])        # (T, E)
    probs = jax.nn.softmax(logits, axis=-1)
    w, idx = jax.lax.top_k(probs, k)                            # (T, k)
    w = w / jnp.maximum(jnp.sum(w, axis=-1, keepdims=True), 1e-9)

    # ---- permute: sort assignments by expert, rank within segment ----
    flat_e = idx.reshape(t * k)
    tok = jnp.repeat(jnp.arange(t, dtype=jnp.int32), k)
    order = jnp.argsort(flat_e, stable=True)
    es = flat_e[order]
    toks = tok[order]
    pos_in = jnp.arange(t * k, dtype=jnp.int32)
    is_start = jnp.concatenate([jnp.array([True]), es[1:] != es[:-1]])
    seg0 = jax.lax.associative_scan(
        jnp.maximum, jnp.where(is_start, pos_in, 0))
    rank = pos_in - seg0

    c = _capacity(cfg, t)
    kept = rank < c
    slot = jnp.where(kept, es * c + rank, e * c)                # OOB = drop

    buf = jnp.zeros((e * c, d), x.dtype)
    buf = buf.at[slot].set(xt[toks], mode="drop")

    # ---- expert FFNs (SwiGLU), batched einsum over the expert axis ----
    h = buf.reshape(e, c, d)
    g = jax.nn.silu(jnp.einsum("ecd,edf->ecf", h,
                               params["wi_gate"].astype(x.dtype)))
    u = jnp.einsum("ecd,edf->ecf", h, params["wi_up"].astype(x.dtype))
    out_e = jnp.einsum("ecf,efd->ecd", g * u,
                       params["wo"].astype(x.dtype)).reshape(e * c, d)

    # ---- unpermute: gather each kept assignment's output, weight, sum ----
    safe_slot = jnp.where(kept, slot, 0)
    y_sorted = jnp.where(kept[:, None], out_e[safe_slot], 0.0)  # (T*k, D)
    w_sorted = w.reshape(t * k)[order]
    contrib = y_sorted * w_sorted[:, None].astype(x.dtype)
    y = jnp.zeros((t, d), x.dtype).at[toks].add(contrib)

    # ---- shared experts (dense path over all tokens) ----
    if cfg.n_shared_experts:
        sp = params["shared"]
        y = y + L.gated_mlp(xt, sp["wi_gate"], sp["wi_up"], sp["wo"])

    # ---- aux: load-balance loss (Switch-style) + drop fraction ----
    me = jnp.mean(jax.nn.one_hot(idx, e, dtype=jnp.float32), axis=(0, 1))
    pe = jnp.mean(probs, axis=0)
    aux = {
        "moe_lb_loss": e * jnp.sum(me * pe),
        "moe_drop_frac": 1.0 - jnp.mean(kept.astype(jnp.float32)),
    }
    return y.reshape(b, s, d), aux
