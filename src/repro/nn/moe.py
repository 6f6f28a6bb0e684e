"""Mixture-of-Experts: top-k token-choice routing with sort-based dispatch.

Works for both assigned MoE archs — grok-1 (8 experts, top-2) and kimi-k2
(384 experts, top-8). GShard-style one-hot dispatch tensors are O(tokens *
E * capacity) and blow up at 384 experts, so dispatch is sort-based instead:

  1. router -> top-k (renormalized) per token
  2. flatten (token, k) slots, stable-sort by expert id
  3. rank-within-expert via exclusive cumsum of expert counts
  4. scatter tokens into a capacity-bounded buffer [E, C, d]   (drop overflow)
  5. batched expert FFN  [E, C, d] @ [E, d, ff] @ [E, ff, d]
  6. gather back per slot, weighted-combine over k

All steps are O(tokens*k) or O(E*C*d*ff); the buffer is sharded over the
"model" axis (expert parallelism) by the distribution layer. Aux losses:
load-balance (Switch) + router z-loss.

Expert FFN is SwiGLU, projections photonic-quantizable — the paper's "FC
layers segmented into 9-MAC chunks" case maps to expert matmuls directly.
"""

from __future__ import annotations

import dataclasses
from typing import NamedTuple, Optional

import jax
import jax.numpy as jnp

from repro.core.quant import WASpec, fake_quant_weight
from repro.nn.module import KeyGen, scaled_init


@dataclasses.dataclass(frozen=True)
class MoEConfig:
    n_experts: int
    top_k: int
    d_model: int
    d_ff: int                     # per-expert hidden
    capacity_factor: float = 1.25
    router_z_coef: float = 1e-3
    balance_coef: float = 1e-2


class MoEOutput(NamedTuple):
    y: jnp.ndarray
    balance_loss: jnp.ndarray
    z_loss: jnp.ndarray
    dropped_fraction: jnp.ndarray


def init_moe(key, cfg: MoEConfig, dtype=jnp.float32):
    kg = KeyGen(key)
    e, d, f = cfg.n_experts, cfg.d_model, cfg.d_ff
    return {
        "router": scaled_init(d)(kg(), (d, e), jnp.float32),
        "w_gate": scaled_init(d)(kg(), (e, d, f), dtype),
        "w_up": scaled_init(d)(kg(), (e, d, f), dtype),
        "w_down": scaled_init(f)(kg(), (e, f, d), dtype),
    }


def _w(p, dtype):
    """Expert weight, possibly in photonic serving storage ({wq, ws})."""
    if isinstance(p, dict):
        return p["wq"].astype(dtype) * p["ws"].astype(dtype)
    return p


def _expert_ffn(params, xb: jnp.ndarray, quant: Optional[WASpec]) -> jnp.ndarray:
    """xb: [E, C, d] -> [E, C, d] (SwiGLU per expert)."""
    wg = _w(params["w_gate"], xb.dtype)
    wu = _w(params["w_up"], xb.dtype)
    wd = _w(params["w_down"], xb.dtype)
    if quant is not None:
        wg = fake_quant_weight(wg.astype(jnp.float32), quant).astype(xb.dtype)
        wu = fake_quant_weight(wu.astype(jnp.float32), quant).astype(xb.dtype)
        wd = fake_quant_weight(wd.astype(jnp.float32), quant).astype(xb.dtype)
    gate = jnp.einsum("ecd,edf->ecf", xb, wg)
    up = jnp.einsum("ecd,edf->ecf", xb, wu)
    h = jax.nn.silu(gate) * up
    return jnp.einsum("ecf,efd->ecd", h, wd)


def moe_ffn(params, x: jnp.ndarray, cfg: MoEConfig,
            quant: Optional[WASpec] = None,
            capacity: Optional[int] = None) -> MoEOutput:
    """x: [B, S, d] -> MoEOutput with y: [B, S, d]."""
    bsz, seq, d = x.shape
    n_tok = bsz * seq
    e, k = cfg.n_experts, cfg.top_k
    n_slot = n_tok * k
    if capacity is None:
        capacity = max(int(n_tok * k / e * cfg.capacity_factor), 1)

    flat = x.reshape(n_tok, d)
    logits = (flat.astype(jnp.float32) @ params["router"])        # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                        # [N, k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    # ---- aux losses ------------------------------------------------------
    me = probs.mean(axis=0)                                       # [E]
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / n_slot)
    balance = cfg.balance_coef * e * jnp.sum(me * ce)
    z = cfg.router_z_coef * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    # ---- sort-based dispatch --------------------------------------------
    flat_e = top_i.reshape(-1)                                    # [N*k]
    flat_w = top_w.reshape(-1)
    src_tok = jnp.arange(n_slot, dtype=jnp.int32) // k
    order = jnp.argsort(flat_e, stable=True)                      # [N*k]
    sorted_e = flat_e[order]
    counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
    seg_start = jnp.cumsum(counts) - counts                       # exclusive
    rank = jnp.arange(n_slot, dtype=jnp.int32) - seg_start[sorted_e]
    slot_sorted = jnp.where(rank < capacity,
                            sorted_e * capacity + rank,
                            e * capacity)                         # drop sentinel
    # slot id per original (token, k) position
    slot = jnp.zeros((n_slot,), jnp.int32).at[order].set(slot_sorted)

    buffer = jnp.zeros((e * capacity, d), x.dtype)
    buffer = buffer.at[slot].set(flat[src_tok], mode="drop")
    yb = _expert_ffn(params, buffer.reshape(e, capacity, d), quant)
    yb = yb.reshape(e * capacity, d)

    gathered = jnp.take(yb, slot, axis=0, fill_value=0.0,
                        mode="fill")                              # [N*k, d]
    combined = (gathered.astype(jnp.float32)
                * flat_w[:, None]).reshape(n_tok, k, d).sum(axis=1)
    dropped = jnp.mean((slot == e * capacity).astype(jnp.float32))
    return MoEOutput(combined.reshape(bsz, seq, d).astype(x.dtype),
                     balance, z, dropped)


def moe_ffn_grouped(params, x: jnp.ndarray, cfg: MoEConfig,
                    quant: Optional[WASpec] = None,
                    capacity: Optional[int] = None,
                    combine_dtype=None) -> MoEOutput:
    """Group-local dispatch: no cross-shard scatter (the §Perf rewrite).

    The sorted dispatch (``moe_ffn``) builds one global [E*C, d] buffer; under
    GSPMD the scatter from data-sharded tokens lowers to a full-buffer
    all-reduce over the data axis (~32 GB/layer for grok/kimi — measured in
    EXPERIMENTS.md §Perf). Here every batch row dispatches *locally*:

      tokens   [G(data), S, d]     (replicated over model)
      buffer   [G(data), E(model), C_g, d]   scatter is group-local
      experts  einsum over the model-sharded E axis — zero-comm matmuls
      combine  gather from yb; SPMD all-gathers yb over model — the ONLY
               collective, ~E*C_g*d per group, optionally quantized to
               ``combine_dtype`` (f8: the CRC trick applied to MoE traffic)

    Per-group capacity C_g = S*k/E * cf keeps expected drop rates identical
    to the global formulation (balance is per-row instead of per-batch).
    """
    from repro.distributed.sharding import shard
    bsz, seq, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    if capacity is None:
        capacity = max(int(seq * k / e * cfg.capacity_factor), 1)
    n_slot = seq * k

    x = shard(x, "batch", None, None)
    logits = (x.astype(jnp.float32) @ params["router"])           # [G,S,E]
    # pin router outputs replicated over model: left free, SPMD shards the
    # E dim on "model" and then all-gathers [G,S,E] f32 back for top_k
    # (~92 GiB/step measured on kimi — §Perf iter 5)
    logits = shard(logits, "batch", None, None)
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, k)                        # [G,S,k]
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)

    me = probs.mean(axis=(0, 1))
    ce = jnp.zeros((e,), jnp.float32).at[top_i.reshape(-1)].add(
        1.0 / (bsz * n_slot))
    balance = cfg.balance_coef * e * jnp.sum(me * ce)
    z = cfg.router_z_coef * jnp.mean(
        jnp.square(jax.nn.logsumexp(logits, axis=-1)))

    def slots_one(eg):
        """eg [S,k] -> slot ids [S*k] in [0, E*C] (E*C == dropped)."""
        flat_e = eg.reshape(-1)
        order = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[order]
        counts = jnp.zeros((e,), jnp.int32).at[flat_e].add(1)
        seg_start = jnp.cumsum(counts) - counts
        rank = jnp.arange(n_slot, dtype=jnp.int32) - seg_start[sorted_e]
        slot_sorted = jnp.where(rank < capacity,
                                sorted_e * capacity + rank, e * capacity)
        return jnp.zeros((n_slot,), jnp.int32).at[order].set(slot_sorted)

    slots = jax.vmap(slots_one)(top_i)                            # [G, S*k]

    y = _moe_block(params, x, slots, top_w, e, k, capacity,
                   quant, combine_dtype)
    dropped = jnp.mean((slots == e * capacity).astype(jnp.float32))
    return MoEOutput(y.astype(x.dtype), balance, z, dropped)


def _moe_block(params, x, slots, top_w, e, k, capacity, quant,
               combine_dtype):
    """Dispatch -> expert FFN -> combine.

    When experts shard over "model", the whole block runs inside ONE
    shard_map region: the dispatch scatter, expert matmuls, combine gather
    AND all their transposes (backward) are local by construction. The only
    mesh traffic is (i) the explicit FSDP all-gather of the local experts'
    weights over "data" (ZeRO-3 semantics; its transpose is the wgrad
    reduce-scatter) and (ii) one token-sized psum over "model". A naive
    GSPMD lowering of the same math moves the full [S*k, d] slot tensor
    through select+all-reduce in BOTH directions — measured 3.4 TB/step on
    kimi-k2 (EXPERIMENTS.md §Perf).
    """
    from repro.distributed.sharding import _current
    from jax.sharding import PartitionSpec as P

    bsz, seq, d = x.shape
    n_slot = slots.shape[-1]
    cur = _current()
    model_axes = cur[1].get("experts") if cur else None
    if cur is None or not model_axes or isinstance(params["w_gate"], dict):
        # unsharded / small-E / quantized-storage fallback (GSPMD)
        def dispatch_one(xg, slot):
            src_tok = jnp.arange(n_slot, dtype=jnp.int32) // k
            buf = jnp.zeros((e * capacity, d), xg.dtype)
            return buf.at[slot].set(xg[src_tok], mode="drop")

        from repro.distributed.sharding import shard as shard_fn
        buffers = jax.vmap(dispatch_one)(x, slots)
        buffers = buffers.reshape(bsz, e, capacity, d)
        buffers = shard_fn(buffers, "batch", "experts", None, None)
        yb = _expert_ffn_grouped(params, buffers, quant)
        if combine_dtype is not None:
            yb = yb.astype(combine_dtype)
        return _combine_fallback(yb, slots, top_w, seq, k)

    mesh, rules = cur
    b_ax = rules.get("batch")
    b0 = (tuple(b_ax) if isinstance(b_ax, tuple) and len(b_ax) > 1
          else (b_ax[0] if isinstance(b_ax, tuple) else b_ax))
    m_ax = model_axes if isinstance(model_axes, str) else model_axes[0]
    d_ax = rules.get("expert_embed")
    d_ax = d_ax[0] if isinstance(d_ax, tuple) else d_ax
    w_flat = top_w.reshape(bsz, n_slot)
    wg_p, wu_p, wd_p = params["w_gate"], params["w_up"], params["w_down"]

    def body(x_l, slot_l, w_l, wg_l, wu_l, wd_l):
        # x_l [G_l,S,d]; slot_l/w_l [G_l,n_slot]; wg_l [E_l, d/dx, f]
        if d_ax is not None:     # explicit ZeRO-3 gather of local experts
            wg = jax.lax.all_gather(wg_l, d_ax, axis=1, tiled=True)
            wu = jax.lax.all_gather(wu_l, d_ax, axis=1, tiled=True)
            wd = jax.lax.all_gather(wd_l, d_ax, axis=2, tiled=True)
        else:
            wg, wu, wd = wg_l, wu_l, wd_l
        e_l = wg.shape[0]
        local_size = e_l * capacity
        base = jax.lax.axis_index(m_ax) * local_size
        loc = slot_l - base
        valid = (loc >= 0) & (loc < local_size)
        loc_in = jnp.where(valid, loc, local_size)          # drop sentinel
        src_tok = jnp.arange(n_slot, dtype=jnp.int32) // k

        def scatter_one(xg, lg):
            buf = jnp.zeros((local_size, d), xg.dtype)
            return buf.at[lg].set(xg[src_tok], mode="drop")

        buf = jax.vmap(scatter_one)(x_l, loc_in)            # [G_l, E_l*C, d]
        xb = buf.reshape(-1, e_l, capacity, d)
        gate = jnp.einsum("gecd,edf->gecf", xb, wg.astype(xb.dtype))
        up = jnp.einsum("gecd,edf->gecf", xb, wu.astype(xb.dtype))
        h = jax.nn.silu(gate) * up
        yb = jnp.einsum("gecf,efd->gecd", h, wd.astype(xb.dtype))
        if combine_dtype is not None:
            yb = yb.astype(combine_dtype)
        ybf = yb.reshape(-1, local_size, d)
        g = jax.vmap(lambda f, i: jnp.take(f, jnp.clip(i, 0, local_size - 1),
                                           axis=0))(ybf, loc)
        g = jnp.where(valid[..., None], g, 0).astype(jnp.float32)
        part = (g * w_l[..., None]).reshape(-1, seq, k, d).sum(axis=2)
        return jax.lax.psum(part.astype(x_l.dtype), m_ax)

    w_spec = P(m_ax, d_ax, None)
    wd_spec = P(m_ax, None, d_ax)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(P(b0, None, None), P(b0, None), P(b0, None),
                  w_spec, w_spec, wd_spec),
        out_specs=P(b0, None, None),
        check_vma=False)(x, slots, w_flat, wg_p, wu_p, wd_p)
    return out.astype(jnp.float32)


def _expert_ffn_grouped(params, xb: jnp.ndarray,
                        quant: Optional[WASpec]) -> jnp.ndarray:
    """xb: [G, E, C, d] -> [G, E, C, d]; compute pinned to bf16 carriers."""
    wg = _w(params["w_gate"], xb.dtype)
    wu = _w(params["w_up"], xb.dtype)
    wd = _w(params["w_down"], xb.dtype)
    if quant is not None:
        wg = fake_quant_weight(wg.astype(jnp.float32), quant).astype(xb.dtype)
        wu = fake_quant_weight(wu.astype(jnp.float32), quant).astype(xb.dtype)
        wd = fake_quant_weight(wd.astype(jnp.float32), quant).astype(xb.dtype)
    gate = jnp.einsum("gecd,edf->gecf", xb, wg.astype(xb.dtype))
    up = jnp.einsum("gecd,edf->gecf", xb, wu.astype(xb.dtype))
    h = jax.nn.silu(gate) * up
    return jnp.einsum("gecf,efd->gecd", h, wd.astype(xb.dtype))


def _combine_fallback(yb, slots, top_w, seq: int, k: int):
    bsz, e, capacity_, d = yb.shape
    yb_flat = yb.reshape(bsz, e * capacity_, d)

    def combine_one(ybg, slot, wg):
        g = jnp.take(ybg, slot, axis=0, fill_value=0.0, mode="fill")
        return (g.astype(jnp.float32)
                * wg.reshape(-1)[:, None]).reshape(seq, k, d).sum(axis=1)

    return jax.vmap(combine_one)(yb_flat, slots, top_w)


def moe_ffn_dense_oracle(params, x: jnp.ndarray, cfg: MoEConfig) -> jnp.ndarray:
    """All-experts reference (tests only): y = sum_e gate_e * FFN_e(x)."""
    bsz, seq, d = x.shape
    flat = x.reshape(-1, d)
    logits = flat.astype(jnp.float32) @ params["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    top_w, top_i = jax.lax.top_k(probs, cfg.top_k)
    top_w = top_w / jnp.maximum(top_w.sum(-1, keepdims=True), 1e-9)
    gates = jnp.zeros_like(probs).at[
        jnp.arange(flat.shape[0])[:, None], top_i].set(top_w)
    per_expert = _expert_ffn(
        params, jnp.broadcast_to(flat[None], (cfg.n_experts,) + flat.shape),
        None)                                                    # [E, N, d]
    y = jnp.einsum("ne,end->nd", gates, per_expert.astype(jnp.float32))
    return y.reshape(bsz, seq, d).astype(x.dtype)
