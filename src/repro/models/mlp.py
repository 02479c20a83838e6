"""Feed-forward layers: gated dense MLP and the expert-parallel MoE.

MoE design (DESIGN.md §5): tokens are replicated across the model axis
between blocks (standard TP residual stream), experts are sharded over the
model axis.  Each expert shard therefore dispatches *locally* — it selects,
from the tokens it already holds, those routed to its own experts; no
dispatch collective is needed, and the combine is the same single psum that
Megatron-style TP FFN layers already pay.  Capacity-bounded (GShard-style
"dropping"): per shard, each expert accepts up to
ceil(T_local * top_k / n_experts * capacity) tokens.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig
from repro.distrib.sharding import active_mesh, resolve_spec, shard
from repro.models.common import act_fn, dense_init, split_keys


def init_mlp_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d, f = cfg.d_model, cfg.d_ff
    ks = split_keys(key, 3)
    return {
        "w1": dense_init(ks[0], (d, f), d, dtype),  # gate
        "w3": dense_init(ks[1], (d, f), d, dtype),  # up
        "w2": dense_init(ks[2], (f, d), f, dtype),  # down
    }


def mlp(x, p, cfg: ModelConfig):
    act = act_fn(cfg.act)
    h = act(jnp.einsum("bsd,df->bsf", x, p["w1"])) * jnp.einsum(
        "bsd,df->bsf", x, p["w3"]
    )
    h = shard(h, "batch", "seq", "mlp")
    y = jnp.einsum("bsf,fd->bsd", h, p["w2"])
    return shard(y, "batch", "seq", None)


# ---------------------------------------------------------------------------
# MoE
# ---------------------------------------------------------------------------
def init_moe_params(key, cfg: ModelConfig, dtype=jnp.float32):
    d, f, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    ks = split_keys(key, 4)
    return {
        "router": dense_init(ks[0], (d, E), d, dtype),
        "w1": dense_init(ks[1], (E, d, f), d, dtype),
        "w3": dense_init(ks[2], (E, d, f), d, dtype),
        "w2": dense_init(ks[3], (E, f, d), f, dtype),
    }


def _moe_local(x, p, cfg: ModelConfig, n_shards: int, shard_idx):
    """Per-shard MoE math. x: (b_loc, S, d); p holds this shard's experts
    (E_loc, ...) plus the full (replicated) router."""
    B, S, d = x.shape
    E, k = cfg.n_experts, cfg.top_k
    E_loc = p["w1"].shape[0]
    act = act_fn(cfg.act)
    T = B * S
    xt = x.reshape(T, d)

    logits = jnp.einsum("td,de->te", xt, p["router"]).astype(jnp.float32)
    weights, ids = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), k)  # (T,k)
    weights = weights / jnp.sum(weights, axis=-1, keepdims=True)

    # auxiliary load-balance loss (computed identically on every shard)
    me = jnp.mean(jax.nn.softmax(logits, axis=-1), axis=0)  # (E,)
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(1.0) / (T * k)
    aux = E * jnp.sum(me * ce)

    # local expert range for this shard
    lo = shard_idx * E_loc
    ids_l = ids - lo  # (T, k), valid iff in [0, E_loc)
    in_range = (ids_l >= 0) & (ids_l < E_loc)
    flat_ids = jnp.where(in_range, ids_l, E_loc).reshape(-1)  # (T*k,)

    # capacity floor matters at decode (T small): never drop when T*k is tiny
    cap = max(int((T * k / E) * cfg.moe_capacity) + 1, min(T * k, 32))
    onehot = jax.nn.one_hot(flat_ids, E_loc, dtype=jnp.int32)  # (T*k, E_loc)
    pos = (jnp.cumsum(onehot, axis=0) - onehot)  # position within expert
    my_pos = jnp.take_along_axis(
        pos, jnp.minimum(flat_ids, E_loc - 1)[:, None], axis=1
    )[:, 0]
    keep = in_range.reshape(-1) & (my_pos < cap)

    # Gather-based dispatch (EXPERIMENTS.md §Perf iter 3): scatter only the
    # *assignment indices* into the (E_loc, cap) slot map, then build the
    # expert buffer with a gather.  The combine is a reshape + weighted sum
    # — no (T*k, d)-sized scatter anywhere, which removes the per-element
    # u32 scatter-index tensors XLA materializes for big scatters and keeps
    # the whole path in the compute dtype.
    A = T * k
    tok_of = jnp.repeat(jnp.arange(T), k)
    e_idx = jnp.where(keep, flat_ids, E_loc)  # E_loc = drop row
    slot_src = jnp.full((E_loc + 1, cap), A, jnp.int32)
    slot_src = slot_src.at[e_idx, jnp.where(keep, my_pos, 0)].set(
        jnp.arange(A, dtype=jnp.int32), mode="drop"
    )
    slot_src = slot_src[:E_loc]  # (E_loc, cap); A = empty slot
    slot_tok = jnp.where(slot_src < A, tok_of[jnp.minimum(slot_src, A - 1)], T)
    buf = jnp.where(
        (slot_src < A)[..., None],
        xt[jnp.minimum(slot_tok, T - 1)],
        jnp.zeros((), xt.dtype),
    )  # (E_loc, cap, d)

    h = act(jnp.einsum("ecd,edf->ecf", buf, p["w1"])) * jnp.einsum(
        "ecd,edf->ecf", buf, p["w3"]
    )
    y = jnp.einsum("ecf,efd->ecd", h, p["w2"])  # (E_loc, cap, d)

    # combine: gather each assignment's expert output, weighted sum over k
    y_asg = y[jnp.minimum(e_idx, E_loc - 1), jnp.where(keep, my_pos, 0)]
    w_flat = jnp.where(keep, weights.reshape(-1), 0.0).astype(y.dtype)
    out = (y_asg * w_flat[:, None]).reshape(T, k, d).sum(axis=1)
    return out.reshape(B, S, d), aux


def moe(x, p, cfg: ModelConfig):
    """Expert-parallel MoE. Returns (y, aux_loss)."""
    mesh = active_mesh()
    if mesh is None or "model" not in mesh.axis_names or mesh.shape["model"] == 1:
        y, aux = _moe_local(x, p, cfg, 1, 0)
        return y, aux

    tok_spec = resolve_spec(("batch", None, None))
    # FSDP-style secondary sharding of expert FFN dims over the data axis
    # (rules key "moe_fsdp"): weights are stored (model, data)-sharded and
    # all-gathered per layer at use — ZeRO-3 for the expert store.
    w13_spec = resolve_spec(("experts", None, "moe_fsdp"))
    w2_spec = resolve_spec(("experts", "moe_fsdp", None))
    fsdp = "data" in jax.tree.leaves(w13_spec)
    exp_spec = {
        "router": P(),
        "w1": w13_spec,
        "w3": w13_spec,
        "w2": w2_spec,
    }
    n_shards = mesh.shape["model"]
    assert cfg.n_experts % n_shards == 0, (
        f"{cfg.n_experts} experts not divisible by model={n_shards}"
    )

    def local_fn(x_loc, p_loc):
        idx = jax.lax.axis_index("model")
        if fsdp:
            p_loc = dict(
                p_loc,
                w1=jax.lax.all_gather(p_loc["w1"], "data", axis=2, tiled=True),
                w3=jax.lax.all_gather(p_loc["w3"], "data", axis=2, tiled=True),
                w2=jax.lax.all_gather(p_loc["w2"], "data", axis=1, tiled=True),
            )
        y, aux = _moe_local(x_loc, p_loc, cfg, n_shards, idx)
        y = jax.lax.psum(y, "model")
        aux = jax.lax.pmean(aux, "model")
        return y, aux

    fn = jax.shard_map(
        local_fn,
        mesh=mesh,
        in_specs=(tok_spec, exp_spec),
        out_specs=(tok_spec, P()),
        check_vma=False,
    )
    return fn(x, p)
