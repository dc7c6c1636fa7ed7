"""Mixture-of-Experts layer with sorted, capacity-bounded dispatch.

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
"""
from __future__ import annotations

import math

import jax
import jax.numpy as jnp
import numpy as np

from jax.sharding import PartitionSpec as P

from repro.models import layers as L
from repro.parallel.sharding import constrain, current_mesh, dp_axes


def init_moe_params(kg, cfg, num_layers: int, dtype) -> dict:
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
