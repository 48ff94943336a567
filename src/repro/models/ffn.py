"""FFN blocks: dense SwiGLU / GELU MLP and Mixture-of-Experts.

MoE uses a *grouped sort-based dispatch*: tokens are grouped per sequence
(group axis sharded with batch over the data axes), and within each group
top-k assignments are sorted by expert id and scattered into a fixed
(E, C) capacity buffer. All data-dependent scatter/gather stays *local to
the group*, so under pjit no cross-shard data-dependent communication is
generated — expert weights are tensor-parallel over ``moe_mlp`` and the
only collective is the standard TP all-reduce of the down-projection.
Overflow beyond capacity is dropped (GShard/Switch semantics).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.configs.base import ArchConfig
from repro.models.layers import gelu_mlp, swiglu
from repro.models.params import ParamSpec

__all__ = ["dense_specs", "dense_apply", "moe_specs", "moe_apply"]


# ---------------------------------------------------------------------------
# dense
# ---------------------------------------------------------------------------

def dense_specs(cfg: ArchConfig) -> dict:
    d, ff = cfg.d_model, cfg.d_ff
    dt = cfg.pdtype()
    if cfg.act == "gelu":
        return {
            "w_in": ParamSpec((d, ff), ("embed", "mlp"), dtype=dt),
            "b_in": ParamSpec((ff,), ("mlp",), init="zeros", dtype=dt),
            "w_out": ParamSpec((ff, d), ("mlp", "embed"), dtype=dt),
            "b_out": ParamSpec((d,), ("embed",), init="zeros", dtype=dt),
        }
    return {
        "w_gate": ParamSpec((d, ff), ("embed", "mlp"), dtype=dt),
        "w_up": ParamSpec((d, ff), ("embed", "mlp"), dtype=dt),
        "w_down": ParamSpec((ff, d), ("mlp", "embed"), dtype=dt),
    }


def dense_apply(cfg: ArchConfig, p, x):
    if cfg.act == "gelu":
        return gelu_mlp(x, p["w_in"], p["b_in"], p["w_out"], p["b_out"])
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------

def moe_specs(cfg: ArchConfig) -> dict:
    d, e, mff = cfg.d_model, cfg.num_experts, cfg.moe_d_ff
    dt = cfg.pdtype()
    out = {
        "router": ParamSpec((d, e), ("embed", "experts"), dtype=dt, scale=0.02),
        "w_gate": ParamSpec((e, d, mff), ("experts", "embed", "moe_mlp"), dtype=dt),
        "w_up": ParamSpec((e, d, mff), ("experts", "embed", "moe_mlp"), dtype=dt),
        "w_down": ParamSpec((e, mff, d), ("experts", "moe_mlp", "embed"), dtype=dt),
    }
    if cfg.num_shared_experts:
        sff = cfg.num_shared_experts * mff
        out["shared"] = {
            "w_gate": ParamSpec((d, sff), ("embed", "mlp"), dtype=dt),
            "w_up": ParamSpec((d, sff), ("embed", "mlp"), dtype=dt),
            "w_down": ParamSpec((sff, d), ("mlp", "embed"), dtype=dt),
        }
    return out


def _capacity(tokens_per_group: int, top_k: int, num_experts: int, cf: float) -> int:
    c = math.ceil(tokens_per_group * top_k * cf / num_experts)
    return max(int(c), 1)


def _group_dispatch(xg, gates_g, idx_g, p, cfg: ArchConfig, capacity: int):
    """MoE for ONE group. xg: (T, d); gates/idx: (T, k). Returns (T, d)."""
    t, d = xg.shape
    k = idx_g.shape[-1]
    e = cfg.num_experts
    cd = cfg.cdtype()

    flat_e = idx_g.reshape(-1)                       # (T*k,)
    flat_t = jnp.arange(t * k) // k                  # (T*k,) token of each slot
    flat_g = gates_g.reshape(-1)

    order = jnp.argsort(flat_e)
    se, st, sg = flat_e[order], flat_t[order], flat_g[order]
    counts = jnp.bincount(se, length=e)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(t * k) - starts[se]
    keep = pos_in_e < capacity
    slot = jnp.where(keep, se * capacity + pos_in_e, e * capacity)  # OOB -> drop

    buf = jnp.zeros((e * capacity, d), cd).at[slot].set(xg[st].astype(cd), mode="drop")
    buf = buf.reshape(e, capacity, d)

    g = jnp.einsum("ecd,edf->ecf", buf, p["w_gate"].astype(cd))
    u = jnp.einsum("ecd,edf->ecf", buf, p["w_up"].astype(cd))
    y_buf = jnp.einsum("ecf,efd->ecd", jax.nn.silu(g) * u, p["w_down"].astype(cd))

    y_tok = y_buf.reshape(e * capacity, d)
    y_sorted = jnp.take(y_tok, jnp.minimum(slot, e * capacity - 1), axis=0)
    y_sorted = y_sorted * (sg * keep).astype(cd)[:, None]
    return jnp.zeros((t, d), cd).at[st].add(y_sorted)


def _routed_vmap(x, gates, idx, p, cfg: ArchConfig, capacity: int):
    return jax.vmap(
        lambda xg, gg, ig: _group_dispatch(xg, gg, ig, p, cfg, capacity)
    )(x, gates, idx)


def moe_apply(cfg: ArchConfig, p, x, *, train: bool = False):
    """x: (B, S, d) -> (B, S, d), plus load-balance aux loss (f32 scalar).

    The data-dependent sort/scatter dispatch is wrapped in a ``shard_map``
    manual over the batch mesh axes (model axis stays auto for the expert
    TP einsums): under plain pjit, GSPMD cannot keep the scatter sharded
    and replicates the dispatch buffers on every device (~135 GB/chip for
    deepseek prefill_32k) then all-reduces them. With the batch axes manual
    the dispatch is provably local per shard and the only collective left
    is the TP all-reduce of the down-projection. See EXPERIMENTS.md §Perf.
    """
    from jax.sharding import PartitionSpec as P

    b, s, d = x.shape
    cd = cfg.cdtype()
    logits = jnp.einsum("bsd,de->bse", x, p["router"].astype(cd)).astype(jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    gates, idx = jax.lax.top_k(probs, cfg.top_k)
    gates = gates / jnp.maximum(gates.sum(-1, keepdims=True), 1e-9)

    # load-balance aux (Switch eq. 4): E * sum_e f_e * p_e
    onehot = jax.nn.one_hot(idx, cfg.num_experts, dtype=jnp.float32)  # (B,S,k,E)
    frac_tokens = onehot.sum(2).mean((0, 1))
    frac_prob = probs.mean((0, 1))
    aux = cfg.num_experts * jnp.sum(frac_tokens * frac_prob)

    capacity = _capacity(s, cfg.top_k, cfg.num_experts, cfg.capacity_factor)
    gates = gates.astype(cd)

    am = jax.sharding.get_abstract_mesh()
    batch_axes = tuple(a for a in ("pod", "data") if a in am.axis_names)
    n_shards = 1
    for a in batch_axes:
        n_shards *= am.shape[a]
    # train gating: shard_map inside a rematerialized scan bwd currently
    # aborts XLA's SPMD partitioner (CloneAllReduce "Invalid binary
    # instruction opcode copy", XLA bug b/433785288); the serving paths
    # (prefill/decode) are proven and keep the fix. See EXPERIMENTS §Perf.
    if cfg.moe_shard_map and not train and batch_axes and b % n_shards == 0:
        spec = P(batch_axes, None, None)
        routed = jax.shard_map(
            lambda xg, gg, ig, pp: _routed_vmap(xg, gg, ig, pp, cfg, capacity),
            mesh=am,
            in_specs=(spec, spec, spec, P()),
            out_specs=spec,
            axis_names=set(batch_axes),
            check_vma=False,
        )(x, gates, idx, {k: p[k] for k in ("w_gate", "w_up", "w_down")})
    else:
        routed = _routed_vmap(x, gates, idx, p, cfg, capacity)

    out = routed
    if cfg.num_shared_experts:
        sp = p["shared"]
        out = out + swiglu(x, sp["w_gate"], sp["w_up"], sp["w_down"])
    return out.astype(x.dtype), aux
