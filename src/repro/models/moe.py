"""Mixture-of-Experts layers: sorted, capacity-bounded dispatch, and a
no-drop grouped layer for an expert-parallel share.

The top-k routing indirection is the LM-family instance of the paper's C2
(indirect streams): runtime indices drive a gather -> dense compute -> scatter
pipeline. Dispatch is performed *per batch row* so the token sort is local to
the row — under pjit with batch-sharded activations every device sorts only
its own tokens (no cross-device sort), mirroring how Occamy clusters handle
their local SPM tile before DMA-ing results out.

Experts are TP-sharded on d_ff over the `model` axis (all experts resident on
every model-group, like the paper's group-replicated left matrices); an
all-to-all expert-parallel variant lives in parallel/collectives.py and is
exercised in the §Perf hillclimb.

``moe_grouped``, which the sigmoid/bias router selects
(``router="sigmoid_bias"``, ``cfg.grouped_moe``), is the serving layer of a
device that holds a contiguous range of the routed experts
(``cfg.held_experts``): the router scores all E experts, every token's
top-k rows that fall on a held expert are sorted by expert and computed by
a grouped matmul (``jax.lax.ragged_dot``) with no capacity, so no token is
dropped; rows routed to experts held elsewhere are left out, and the shared
experts (a dense SwiGLU) are added for every token. Summed over the
devices of an expert-parallel group, the routed parts give the whole layer.
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.parallel.sharding import constrain, current_mesh, dp_axes


# the grouped layer's fields, which the capacity layer does not read
GROUPED_ONLY = ("routed_scale", "moe_d_ff", "num_shared_experts",
                "experts_held", "experts_first")


def init_moe_params(kg, cfg, num_layers: int, dtype) -> dict:
    if cfg.router != "softmax":
        raise ValueError(f"unknown router {cfg.router!r}")
    kept = [f for f in GROUPED_ONLY
            if getattr(cfg, f) != type(cfg).__dataclass_fields__[f].default]
    if kept:
        raise ValueError(f"{cfg.name}: the softmax router's capacity layer "
                         f"does not read {kept}; they need "
                         "router='sigmoid_bias'")
    d, f, E = cfg.d_model, cfg.d_ff, cfg.num_experts
    fm = 2 if L.is_gated(cfg.activation) else 1
    p = {
        "router": L.dense_init(kg(), (num_layers, d, E), dtype=jnp.float32),
        "moe_wi": L.dense_init(kg(), (num_layers, E, d, f), dtype=dtype),
        "moe_wo": L.dense_init(
            kg(), (num_layers, E, f, d), scale=1.0 / math.sqrt(f), dtype=dtype
        ),
    }
    if fm == 2:
        p["moe_wg"] = L.dense_init(kg(), (num_layers, E, d, f), dtype=dtype)
    return p


def capacity(cfg, seq_len: int) -> int:
    E, k = cfg.num_experts, cfg.experts_per_token
    return max(int(math.ceil(k * seq_len / E * cfg.capacity_factor)), 1)


def _route(p, x, cfg):
    """Router logits/probs in fp32. x: (..., d) -> (probs, topv, topi, aux)."""
    E, k = cfg.num_experts, cfg.experts_per_token
    logits = jnp.einsum(
        "...d,de->...e", x.astype(jnp.float32), p["router"]
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topv, topi = jax.lax.top_k(probs, k)
    topv = topv / jnp.maximum(topv.sum(-1, keepdims=True), 1e-9)
    # Switch-style load-balance aux loss
    hits = jax.nn.one_hot(topi, E, dtype=jnp.float32).sum(-2)  # (..., E)
    f_e = hits.reshape(-1, E).mean(0) / k
    p_e = probs.reshape(-1, E).mean(0)
    aux = E * jnp.sum(f_e * p_e)
    return topv, topi, aux


def moe_mlp(p, x, cfg):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar)."""
    B, S, d = x.shape
    E, k = cfg.num_experts, cfg.experts_per_token
    C = capacity(cfg, S)
    act = L.activation_fn(cfg.activation)
    gated = L.is_gated(cfg.activation)

    # routing scatters/gathers along the token axis: pin it unsharded first
    # (a seq-sharded operand makes GSPMD materialize full-shape u32 index
    # tensors and all-gather them -- the Megatron-SP gather belongs HERE)
    x = constrain(x, "moe_tokens")
    topv, topi, aux = _route(p, x, cfg)

    def dispatch_rows(x_loc, topi_loc):
        """(b, S, d), (b, S, k) -> (b, E, C, d) + combine metadata. LOCAL."""

        def row(xb, ib):
            e_flat = ib.reshape(-1)  # (S*k,)
            order = jnp.argsort(e_flat, stable=True)
            se = e_flat[order]
            first = jnp.searchsorted(se, jnp.arange(E), side="left")
            rank = jnp.arange(S * k) - first[se]
            slot = jnp.where(rank < C, se * C + rank, E * C)  # E*C == dropped
            # ONLY int32 vectors are ever scattered; all value movement is
            # gathers (scatters of (n, d) values make XLA materialize
            # full-width index broadcasts — 45 GB of u32 at grok scale)
            inv = (
                jnp.full((E * C,), S * k, jnp.int32)
                .at[slot]
                .set(jnp.arange(S * k, dtype=jnp.int32), mode="drop")
            )
            tok_sorted = order // k
            src_tok = jnp.where(
                inv < S * k, tok_sorted[jnp.minimum(inv, S * k - 1)], S
            )
            disp = jnp.where(
                (src_tok < S)[:, None],
                xb[jnp.minimum(src_tok, S - 1)],
                0,
            )
            return disp.reshape(E, C, d), slot, order

        return jax.vmap(row)(x_loc, topi_loc)

    def combine_rows(y_loc, slot_loc, order_loc, topv_loc):
        """(b, E, C, d), metadata -> (b, S, d). LOCAL."""

        def row(yb, slotb, orderb, vb):
            yf = yb.reshape(E * C, d)
            live = (slotb < E * C)[:, None]
            vals = jnp.where(live, yf[jnp.minimum(slotb, E * C - 1)], 0)
            # inverse permutation via int-only scatter, then gather
            inv_order = (
                jnp.zeros((S * k,), jnp.int32)
                .at[orderb]
                .set(jnp.arange(S * k, dtype=jnp.int32))
            )
            out = vals[inv_order]
            return (out * vb.reshape(-1)[:, None]).reshape(S, k, d).sum(1)

        return jax.vmap(row)(y_loc, slot_loc, order_loc, topv_loc)

    # The dispatch sort/scatter must stay device-LOCAL: under plain pjit,
    # GSPMD shards the sort intermediates over `model` and then materializes
    # and all-gathers full-shape u32 index tensors. shard_map over the dp
    # axes makes locality structural (each "cluster" handles its own SPM
    # tile, paper Sec. III-B); expert einsums stay outside for TP.
    mesh = current_mesh()
    use_shard_map = mesh is not None and B % (
        int(np.prod([mesh.shape[a] for a in dp_axes(mesh)]))
    ) == 0
    if use_shard_map:
        dp = dp_axes(mesh)
        disp, slot, order = jax.shard_map(
            dispatch_rows,
            mesh=mesh,
            in_specs=(P(dp, None, None), P(dp, None, None)),
            out_specs=(P(dp, None, None, None), P(dp, None), P(dp, None)),
            check_vma=False,
        )(x, topi)
    else:
        disp, slot, order = dispatch_rows(x, topi)
    disp = constrain(disp, "moe_dispatch")

    # tp_reduce_bf16 extends to the hidden activations: the (B,E,C,f)
    # buffers are the largest tensors in the step; bf16 halves their traffic
    # (MXU accumulates fp32 internally regardless of the output dtype)
    h_dt = jnp.dtype(x.dtype) if cfg.tp_reduce_bf16 else jnp.float32
    h = jnp.einsum(
        "becd,edf->becf", disp, p["moe_wi"], preferred_element_type=h_dt
    )
    h = constrain(h, "moe_hidden")
    if gated:
        g = jnp.einsum(
            "becd,edf->becf", disp, p["moe_wg"], preferred_element_type=h_dt
        )
        h = act(constrain(g, "moe_hidden").astype(jnp.float32)).astype(h_dt) * h
    h = h.astype(x.dtype)
    # tp_reduce_bf16: emit the expert output in bf16 so the TP all-reduce
    # over `model` moves half the bytes (local MXU accumulation is fp32
    # either way; only the cross-device reduction is lower precision)
    y_dt = jnp.dtype(x.dtype) if cfg.tp_reduce_bf16 else jnp.float32
    y = jnp.einsum(
        "becf,efd->becd", h, p["moe_wo"], preferred_element_type=y_dt
    ).astype(x.dtype)
    y = constrain(y, "moe_dispatch")

    if use_shard_map:
        out = jax.shard_map(
            combine_rows,
            mesh=mesh,
            in_specs=(P(dp, None, None, None), P(dp, None), P(dp, None),
                      P(dp, None, None)),
            out_specs=P(dp, None, None),
            check_vma=False,
        )(y, slot, order, topv.astype(x.dtype))
    else:
        out = combine_rows(y, slot, order, topv.astype(x.dtype))
    return out, aux * cfg.router_aux_weight


def moe_mlp_decode(p, x, cfg):
    """Decode path: (B, d). All experts computed; with a full batch every
    expert's weights stream from HBM anyway, so this costs no extra memory
    traffic (decode is weight-bound)."""
    E = cfg.num_experts
    act = L.activation_fn(cfg.activation)
    gated = L.is_gated(cfg.activation)
    topv, topi, _ = _route(p, x, cfg)
    w = (jax.nn.one_hot(topi, E, dtype=jnp.float32) * topv[..., None]).sum(-2)
    h = jnp.einsum(
        "bd,edf->bef", x, p["moe_wi"], preferred_element_type=jnp.float32
    )
    if gated:
        g = jnp.einsum(
            "bd,edf->bef", x, p["moe_wg"], preferred_element_type=jnp.float32
        )
        h = act(g) * h
    h = h.astype(x.dtype)
    y = jnp.einsum(
        "bef,efd->bed", h, p["moe_wo"], preferred_element_type=jnp.float32
    )
    return jnp.einsum("bed,be->bd", y, w).astype(x.dtype), jnp.float32(0.0)


# ---------------------------------------------------------------------------
# grouped, no-drop dispatch over the held experts (serving)
# ---------------------------------------------------------------------------


def init_grouped_moe_params(kg, cfg, num_layers: int, dtype) -> dict:
    """The grouped layer's leaves, stacked over ``num_layers``: the router
    over all E experts (and its correction bias), the held experts only,
    and the shared experts as one SwiGLU of ``num_shared * moe_d_ff``."""
    d, f, E = cfg.d_model, cfg.resolved_moe_d_ff(), cfg.num_experts
    _, n = cfg.held_experts
    fs = cfg.num_shared_experts * f
    p = {
        "router": L.dense_init(kg(), (num_layers, d, E), dtype=dtype),
        "moe_wi": L.dense_init(kg(), (num_layers, n, d, f), dtype=dtype),
        "moe_wg": L.dense_init(kg(), (num_layers, n, d, f), dtype=dtype),
        "moe_wo": L.dense_init(
            kg(), (num_layers, n, f, d), scale=1.0 / math.sqrt(f), dtype=dtype
        ),
        "router_bias": L.dense_init(kg(), (num_layers, E), scale=0.05,
                                    dtype=dtype),
    }
    if fs:
        p["shared_wi"] = L.dense_init(kg(), (num_layers, d, fs), dtype=dtype)
        p["shared_wg"] = L.dense_init(kg(), (num_layers, d, fs), dtype=dtype)
        p["shared_wo"] = L.dense_init(
            kg(), (num_layers, fs, d), scale=1.0 / math.sqrt(fs), dtype=dtype
        )
    return p


def route_topk(p, x, cfg):
    """(weights (N, k) f32, expert ids (N, k)) over all E experts, in f32,
    by DeepSeek-V3's ``noaux_tc`` rule with one group: scores
    ``s = sigmoid(x W_r)``, the top k chosen on ``s + b``, weighted by ``s``
    at the chosen experts over their sum, times ``routed_scale``."""
    k = cfg.experts_per_token
    logits = jnp.einsum("nd,de->ne", x.astype(jnp.float32),
                        p["router"].astype(jnp.float32))
    s = jax.nn.sigmoid(logits)
    _, topi = jax.lax.top_k(s + p["router_bias"].astype(jnp.float32), k)
    w = jnp.take_along_axis(s, topi, axis=-1)
    return w / w.sum(-1, keepdims=True) * cfg.routed_scale, topi


def held_experts(p, x, w, topi, cfg, rows=None):
    """The held experts' part of the routed output, (N, d) f32, and the
    rows each held expert received, (n,) int32.

    The (token, choice) pairs that fall on a held expert, and whose token
    is one of ``rows`` (a (N,) bool mask; all when None), are sorted by
    expert and computed by grouped matmuls; every other pair goes to a
    sink group past the held ones, which no matmul reads, and adds 0."""
    first, n = cfg.held_experts
    N, k = topi.shape
    act = L.activation_fn(cfg.activation)
    local = topi - first
    take = (local >= 0) & (local < n)
    if rows is not None:
        take = take & rows[:, None]
    e = jnp.where(take, local, n).reshape(-1)
    order = jnp.argsort(e, stable=True)
    sizes = jnp.zeros((n + 1,), jnp.int32).at[e].add(1)[:n]
    xs = x[order // k]  # (N*k, d), grouped by expert
    h = jax.lax.ragged_dot(xs, p["moe_wi"], sizes,
                           preferred_element_type=jnp.float32)
    g = jax.lax.ragged_dot(xs, p["moe_wg"], sizes,
                           preferred_element_type=jnp.float32)
    u = (act(g) * h).astype(x.dtype)
    y = jax.lax.ragged_dot(u, p["moe_wo"], sizes,
                           preferred_element_type=jnp.float32)
    # rows past the held groups belong to no group: what a grouped matmul
    # leaves there is not read
    live = jnp.arange(N * k) < sizes.sum()
    y = jnp.where(live[:, None], y * w.reshape(-1)[order][:, None], 0.0)
    inv = jnp.zeros((N * k,), jnp.int32).at[order].set(
        jnp.arange(N * k, dtype=jnp.int32))
    return y[inv].reshape(N, k, -1).sum(1), sizes


def moe_grouped(p, x, cfg, rows=None):
    """x: (N, d) -> (out (N, d), rows per held expert (n,) int32): the
    routed part of the held experts plus the shared experts."""
    with jax.named_scope("moe_route"):
        w, topi = route_topk(p, x, cfg)
    with jax.named_scope("moe_experts"):
        routed, sizes = held_experts(p, x, w, topi, cfg, rows)
    if "shared_wi" in p:
        with jax.named_scope("moe_shared"):
            shared = L.mlp({"wi": p["shared_wi"], "wg": p["shared_wg"],
                            "wo": p["shared_wo"]}, x[None], cfg.activation)[0]
        routed = routed + shared.astype(jnp.float32)
    return routed.astype(x.dtype), sizes
