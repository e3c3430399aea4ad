"""Mixture-of-Experts FFN: two layers.

:func:`expert_layer` (serving) is dropless and holds a share of the
experts: it routes every token over all of the router's experts, keeps the
(token, expert) pairs that fall on the experts it holds, and computes them
with a grouped matmul over the pairs sorted by expert
(``kernels/moe_gmm``), however many a batch sends to one expert; so a
served token's result never depends on its batch.  The experts it does not
hold add nothing: on a chip of an expert-parallel deployment their share
comes from the other chips.

:func:`moe_ffn` (sharded training) routes with per-group capacity:

**Grouped routing** (t5x/GShard ``num_groups`` style): tokens are split into
G groups (G = the mesh's data-parallel extent, so each group is resident on
one data shard) and routed *independently* per group with per-group
capacity.  Every routing/cumsum/gather/combine op then has a leading
group axis sharded over 'data', so dispatch is **local** to the shard; the
only cross-device movement is the expert einsum's token<->expert exchange
(experts shard over the model axis).  With global (ungrouped) routing,
GSPMD lowers the cross-shard slot gather as masked-gather + giant
all-reduces — observed 3.4 TB/step/device on granite train_4k before this
change (EXPERIMENTS.md §Perf iteration 2).

Dropped tokens (over per-group capacity) contribute zero — standard Switch
behaviour.  Returns the load-balancing aux loss alongside the output.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.dist.sharding import mesh_context, shard

__all__ = ["moe_ffn", "expert_layer", "init_moe_params"]


def init_moe_params(key, d_model: int, d_ff: int, n_experts: int, dtype,
                    router_experts: int | None = None):
    """Router over ``router_experts`` (default: ``n_experts``, all held),
    float32, and the ``n_experts`` experts held."""
    k1, k2, k3, k4 = jax.random.split(key, 4)
    s_in = d_model ** -0.5
    s_out = d_ff ** -0.5
    R = router_experts or n_experts
    return {
        "router": (jax.random.normal(k1, (d_model, R)) * s_in).astype(jnp.float32),
        "w_gate": (jax.random.normal(k2, (n_experts, d_model, d_ff)) * s_in).astype(dtype),
        "w_up": (jax.random.normal(k3, (n_experts, d_model, d_ff)) * s_in).astype(dtype),
        "w_down": (jax.random.normal(k4, (n_experts, d_ff, d_model)) * s_out).astype(dtype),
    }


def _infer_groups(T: int) -> int:
    """Groups = data-parallel extent when it divides the tokens (each group
    lives on one data shard); 1 otherwise (single-device tests)."""
    ctx = mesh_context()
    if ctx is None:
        return 1
    dp = ctx.extent(ctx.resolve("batch"))
    return dp if dp > 1 and T % dp == 0 else 1


def moe_ffn(
    params,
    x: jax.Array,  # [B, S, D]
    *,
    top_k: int,
    capacity_factor: float = 1.25,
    act: str = "silu",
    n_groups: int | None = None,
):
    """Returns (out [B, S, D], aux_loss scalar)."""
    B, S, D = x.shape
    T = B * S
    E = params["router"].shape[-1]
    if params["w_gate"].shape[0] != E:
        raise ValueError("capacity routing needs every routed expert held "
                         f"({params['w_gate'].shape[0]} held of {E})")
    G = n_groups or _infer_groups(T)
    Tg = T // G
    # per-group slots per expert; multiple of 8 keeps lanes aligned
    capacity = max(top_k, int(round(Tg * top_k * capacity_factor / E)))
    if Tg >= 8:
        capacity = -(-capacity // 8) * 8

    xg = x.reshape(G, Tg, D)
    xg = shard(xg, "batch", None, None)

    # --- routing (fp32), all ops carry the leading G axis ---
    logits = jnp.einsum("gtd,de->gte", xg.astype(jnp.float32), params["router"])
    probs = jax.nn.softmax(logits, axis=-1)
    gate_vals, expert_idx = jax.lax.top_k(probs, top_k)  # [G, Tg, k]
    gate_vals = gate_vals / jnp.maximum(gate_vals.sum(-1, keepdims=True), 1e-9)

    # load-balancing loss (Switch eq.4): E * sum_e f_e * p_e, averaged over groups
    me = probs.mean(axis=1)                               # [G, E]

    # --- per-group capacity assignment (cumsum within the group) ---
    g_iota = jnp.arange(G, dtype=jnp.int32)[:, None]      # [G, 1]
    counts = jnp.zeros((G, E), jnp.int32)
    frac = jnp.zeros((G, E), jnp.float32)
    slot_tok = jnp.zeros((G, E, capacity + 1), jnp.int32)  # last col = trash
    positions, keep_masks = [], []
    for r in range(top_k):
        e_r = expert_idx[..., r]                          # [G, Tg]
        onehot = jax.nn.one_hot(e_r, E, dtype=jnp.int32)  # [G, Tg, E]
        frac = frac + onehot.sum(1).astype(jnp.float32)
        pos_in_e = (jnp.cumsum(onehot, axis=1) - 1) * onehot
        pos_r = pos_in_e.sum(-1) + jnp.take_along_axis(counts, e_r, axis=1)
        counts = counts + onehot.sum(1)
        within = pos_r < capacity
        pos_r = jnp.where(within, pos_r, capacity)        # [G, Tg]
        positions.append(pos_r)
        keep_masks.append(within)
        slot_tok = slot_tok.at[g_iota, e_r, pos_r].set(
            jnp.broadcast_to(jnp.arange(Tg, dtype=jnp.int32)[None], (G, Tg))
        )

    aux_loss = E * jnp.mean(jnp.sum(me * (frac / (Tg * top_k)), axis=-1))

    # --- expert computation over locally gathered slots ---
    # experts = the Graphi "executor groups" (EP over the model axis);
    # groups shard over data, so the gather below is shard-local and only
    # the expert einsum moves tokens across the mesh.
    src = slot_tok[:, :, :capacity]                       # [G, E, C]
    xin = jax.vmap(lambda xr, sr: xr[sr.reshape(-1)])(xg, src)  # batched local gather
    xin = xin.reshape(G, E, capacity, D)
    xin = shard(xin, "batch", "model", None, None)

    h = jnp.einsum("gecd,edf->gecf", xin, params["w_gate"])
    h = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
    u = jnp.einsum("gecd,edf->gecf", xin, params["w_up"])
    y = jnp.einsum("gecf,efd->gecd", h * u, params["w_down"])  # [G, E, C, D]
    y = shard(y, "batch", "model", None, None)

    # --- combine: token t pulls its slot output, weighted by its gate ---
    # (the cross-shard combine lowers to a masked gather + f32 tuple
    # all-reduce over the expert axis; attempts to steer it to bf16 via
    # dtype/constraint placement did not change the lowering — see
    # EXPERIMENTS.md §Perf iteration A2, refuted)
    out = jnp.zeros((G, Tg, D), jnp.float32)
    flat_y = y.reshape(G, E * capacity, D)
    for r in range(top_k):
        e_r = expert_idx[..., r]
        pos_r = jnp.minimum(positions[r], capacity - 1)
        idx = e_r * capacity + pos_r                      # [G, Tg]
        y_r = jax.vmap(lambda yr, ir: yr[ir])(flat_y, idx)  # [G, Tg, D]
        w = (gate_vals[..., r] * keep_masks[r]).astype(jnp.float32)
        out = out + w[..., None] * y_r.astype(jnp.float32)

    return out.reshape(B, S, D).astype(x.dtype), aux_loss


def expert_layer(
    params,
    x: jax.Array,                     # [B, S, D]
    *,
    top_k: int,
    first_expert: int = 0,
    act: str = "silu",
    token_mask: jax.Array | None = None,   # [B, S]; False: idle or padding
    layer: jax.Array | None = None,
):
    """Dropless top-k experts over the ones held (module docstring).

    The router (float32, ``[D, R]``) scores all ``R`` experts; softmax, the
    top ``top_k``, renormalised over them.  The held experts are
    ``first_expert .. first_expert + E - 1``, ``E`` the experts' leading
    axis; with ``layer`` given, the experts' matrices are every layer's,
    ``[L, E, ...]``, and the grouped matmul reads that layer's in place.
    Tokens masked out route nowhere.  Returns (out [B, S, D], int32
    [2]: the routed pairs that fell on held experts, and the held experts
    that got at least one)."""
    from repro.kernels.moe_gmm import moe_gmm

    B, S, D = x.shape
    T = B * S
    E = params["w_gate"].shape[0 if layer is None else 1]
    with jax.named_scope("repro.moe"):
        xt = x.reshape(T, D)
        logits = jnp.einsum("td,de->te", xt.astype(jnp.float32), params["router"],
                            precision=jax.lax.Precision.HIGHEST)
        gate, idx = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        gate = gate / gate.sum(axis=-1, keepdims=True)
        local = idx - first_expert
        held = (local >= 0) & (local < E)
        if token_mask is not None:
            held &= token_mask.reshape(T, 1)
        # pairs sorted by held expert; the others go last, past every group
        key = jnp.where(held, local, E).reshape(-1)
        order = jnp.argsort(key, stable=True)
        sizes = jnp.zeros((E + 1,), jnp.int32).at[key].add(1)[:E]
        tok = order // top_k
        rows = xt[tok]
        h = moe_gmm(rows, params["w_gate"], sizes, layer=layer)
        h = jax.nn.silu(h) if act == "silu" else jax.nn.gelu(h)
        h = h * moe_gmm(rows, params["w_up"], sizes, layer=layer)
        y = moe_gmm(h, params["w_down"], sizes, layer=layer)
        w = jnp.where(held, gate, 0.0).reshape(-1)[order]
        out = jnp.zeros((T, D), jnp.float32).at[tok].add(
            y.astype(jnp.float32) * w[:, None])
        counts = jnp.stack([sizes.sum(), (sizes > 0).sum()]).astype(jnp.int32)
    return out.reshape(B, S, D).astype(x.dtype), counts
