"""Explicit-collective MoE dispatch (the Megatron-MoE / EP schedule).

GSPMD lowers the einsum-formulated MoE dispatch (``layers.moe``) through
data-dependent scatters, which the CPU partitioner (and sometimes the TPU
one) turns into replication-heavy all-reduces -- the dominant term in the
arctic-480b baseline (§Perf pick 2).  This module expresses the *correct*
schedule by hand with ``jax.shard_map``:

layout (the ``moe_ep``/``moe_a2a`` rule variant):
    tokens : batch sharded over the data axes, d_model full
    experts: sharded over the model axis  (E_loc = E / n_model)
    expert FFN dim (f): sharded over the data axes (f_loc = f / n_data)

per-device schedule (all collectives explicit, all O(tokens), not O(weights)):
    1. route + pack LOCAL tokens into (E, cap_loc, d)      -- no communication
    2. slice my model-shard's experts  (E_loc, cap_loc, d) -- free
    3. all_gather over data: every f-shard needs every token that hits its
       experts                                   (E_loc, n_data*cap_loc, d)
    4. expert matmuls with local weight shards (d full, f_loc)
    5. psum_scatter over data: sum f-partials, keep my tokens' slice
                                                  (E_loc, cap_loc, d)
    6. unpack + weight locally, psum over model: every expert shard
       contributes its experts' outputs to my tokens        (n_loc, d)

Collective bytes per layer-pass per device ~ a few hundred MB of *token*
traffic vs ~1.7 GB of *weight* gathers (arctic) for the FSDP alternative --
the §Perf pick-2 napkin, now implemented rather than estimated.

Differentiable end to end (shard_map collectives have transposes).
"""

from __future__ import annotations

import math
from functools import partial
from typing import Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.parallel.sharding import current_mesh, current_rules, logical_to_spec

Params = dict


def _axis_size(mesh, axes) -> int:
    if axes is None:
        return 1
    if isinstance(axes, str):
        axes = (axes,)
    n = 1
    for a in axes:
        n *= mesh.shape[a]
    return n


def a2a_available(cfg) -> bool:
    """True when the ambient mesh/rules support the explicit EP schedule."""
    mesh, rules = current_mesh(), current_rules()
    if mesh is None or rules is None:
        return False
    e_ax = rules.lookup("expert")
    if isinstance(e_ax, tuple) or e_ax not in mesh.axis_names:
        return False
    n_model = mesh.shape[e_ax]
    return cfg.n_experts % n_model == 0


def moe_a2a(p: Params, cfg, x: jnp.ndarray) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Drop-in for ``layers.moe`` under the explicit EP schedule."""
    mesh, rules = current_mesh(), current_rules()
    e_ax = rules.lookup("expert")                       # e.g. "model"
    f_ax = rules.lookup("tensor")                       # e.g. "data"/None
    b_ax = logical_to_spec(("batch",), mesh, rules)[0]  # data axes (filtered)
    n_model = mesh.shape[e_ax]
    n_data = _axis_size(mesh, f_ax)

    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.top_k
    e_loc = e // n_model
    n_loc = (b * s) // _axis_size(mesh, b_ax)
    cap_loc = max(1, int(math.ceil(n_loc * k * cfg.capacity_factor / e)))

    x_spec = P(b_ax, None, None)
    gate_spec = P(e_ax, None, f_ax)
    down_spec = P(e_ax, f_ax, None)

    def local(x_l, router, gate_l, up_l, down_l):
        bl, sl, _ = x_l.shape
        n = bl * sl
        xf = x_l.reshape(n, d)

        # 1. local routing + pack (identical math to layers.moe, all local)
        logits = xf.astype(jnp.float32) @ router
        probs = jax.nn.softmax(logits, axis=-1)
        topw, topi = jax.lax.top_k(probs, k)
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)

        eid = topi.reshape(n * k)
        w = topw.reshape(n * k)
        tok = jnp.arange(n * k, dtype=jnp.int32) // k
        order = jnp.argsort(eid)
        eid_s, w_s, tok_s = eid[order], w[order], tok[order]
        counts = jnp.zeros((e,), jnp.int32).at[eid].add(1)
        offsets = jnp.concatenate(
            [jnp.zeros((1,), jnp.int32), jnp.cumsum(counts)[:-1]])
        rank = jnp.arange(n * k, dtype=jnp.int32) - offsets[eid_s]
        in_cap = rank < cap_loc
        rank_c = jnp.where(in_cap, rank, cap_loc)

        xs = jnp.take(xf, tok_s, axis=0).astype(x_l.dtype)
        buf = jnp.zeros((e, cap_loc, d), x_l.dtype).at[eid_s, rank_c].set(
            xs, mode="drop")

        # 2. my model-shard's experts
        j = jax.lax.axis_index(e_ax)
        buf_my = jax.lax.dynamic_slice(
            buf, (j * e_loc, 0, 0), (e_loc, cap_loc, d))

        # 3. gather tokens across the f-shard axis (token traffic, not weights)
        if f_ax is not None:
            buf_g = jax.lax.all_gather(buf_my, f_ax, axis=1, tiled=True)
        else:
            buf_g = buf_my                              # f unsharded

        # 4. expert matmuls on local weight shards
        hg = jnp.einsum("ecd,edf->ecf", buf_g, gate_l)
        hu = jnp.einsum("ecd,edf->ecf", buf_g, up_l)
        h = jax.nn.silu(hg) * hu
        o_part = jnp.einsum("ecf,efd->ecd", h, down_l)  # partial over f shards

        # 5. reduce f-partials, keep my tokens' slice
        if f_ax is not None:
            o_my = jax.lax.psum_scatter(o_part, f_ax, scatter_dimension=1,
                                        tiled=True)     # (e_loc, cap_loc, d)
        else:
            o_my = o_part

        # 6. unpack my experts' contributions to my tokens, psum over experts
        is_mine = (eid_s >= j * e_loc) & (eid_s < (j + 1) * e_loc)
        eid_rel = jnp.clip(eid_s - j * e_loc, 0, e_loc - 1)
        contrib = o_my[eid_rel, jnp.minimum(rank_c, cap_loc - 1)]
        wgt = (w_s * in_cap * is_mine).astype(jnp.float32)
        y = jnp.zeros((n, d), jnp.float32).at[tok_s].add(
            contrib.astype(jnp.float32) * wgt[:, None])
        y = jax.lax.psum(y.astype(x_l.dtype), e_ax)  # psum token-sized, bf16

        # aux load-balance loss (global over experts; mean over token shards)
        comb = jnp.sum(jax.nn.one_hot(topi, e, dtype=jnp.float32)
                       * topw[..., None], axis=1)
        density = jnp.mean(comb > 0, axis=0)
        mean_prob = jnp.mean(probs, axis=0)
        aux = jnp.sum(density * mean_prob) * e
        axes = [a for a in ((b_ax,) if isinstance(b_ax, str) else (b_ax or ()))]
        if axes:
            aux = jax.lax.pmean(aux, tuple(axes))
        return y.reshape(bl, sl, d), aux

    fn = jax.shard_map(
        local,
        mesh=mesh,
        in_specs=(x_spec, P(None, None), gate_spec, gate_spec, down_spec),
        out_specs=(x_spec, P()),
        check_vma=False,
    )
    return fn(x, p["router"], p["gate"], p["up"], p["down"])
