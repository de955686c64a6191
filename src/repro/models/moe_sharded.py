"""Explicit expert-parallel MoE dispatch (fully-manual shard_map).

§Perf iteration for the MoE cells: the pjit gather/scatter dispatch in
moe.py leaves the token->group permutation to XLA's SPMD partitioner,
which materializes the GLOBAL (T, d_model) token array as f32/u32
all-reduces *inside the layer loop* — measured ~73 TB/device/step on the
kimi train cell (t_coll = 1470 s). This module routes explicitly inside a
fully-manual shard_map over (data..., model):

* tokens: each 'model' shard takes a contiguous 1/n_model slice of the
  local tokens (free: x is model-replicated at entry) — capacity is
  sharded over 'model' instead of expert ff, so expert compute is never
  replicated and there is no TP all-reduce inside the expert FFN;
* experts: owned by data shards when E % n_data == 0 (kimi: 384/16);
  one all_to_all ships per-(src, expert) capacity groups to owners and a
  reverse all_to_all returns outputs. If E < n_data (mixtral: 8), tokens
  never move and every shard computes all experts on its slice;
* weights: stored FSDP-sharded; the in_spec requests them unsharded on
  d/ff, so XLA all-gathers each layer's expert weights on entry (ZeRO-3)
  and reduce-scatters their grads — O(E_local·d·ff) per layer instead of
  O(T·d) token traffic;
* outputs: one all-gather over 'model' re-replicates the (t_local, d)
  slice outputs.

Numerical parity with moe.moe_apply is covered by a subprocess test
(per-source capacity vs global capacity differ only in drop behaviour;
tests use a drop-free capacity factor).
"""
from __future__ import annotations

import functools
from typing import Tuple

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P

from repro.sharding.activations import current_mesh, _fit


def _route(p, cfg, xf):
    e, k = cfg.n_experts, cfg.experts_per_token
    t = xf.shape[0]
    logits = xf.astype(jnp.float32) @ p["router"]
    probs = jax.nn.softmax(logits, axis=-1)
    gates, eidx = lax.top_k(probs, k)
    gates = gates / jnp.maximum(jnp.sum(gates, -1, keepdims=True), 1e-9)
    me = jnp.mean(probs, axis=0)
    disp = jnp.zeros((t, e), jnp.float32).at[
        jnp.arange(t)[:, None], eidx].set(1.0)
    fe = jnp.mean(disp, axis=0)
    return gates, eidx, me, fe


def _group(xf, eidx, e: int, cap: int, k: int):
    t, d = xf.shape
    eflat = eidx.reshape(-1)
    order = jnp.argsort(eflat, stable=True)
    es = eflat[order]
    starts = jnp.searchsorted(es, jnp.arange(e, dtype=es.dtype))
    rank = jnp.arange(t * k, dtype=jnp.int32) - starts[es].astype(jnp.int32)
    dest = jnp.where(rank < cap, es.astype(jnp.int32) * cap + rank, e * cap)
    src_tok = (order // k).astype(jnp.int32)
    grouped = jnp.zeros((e * cap, d), xf.dtype).at[dest].set(
        xf[src_tok], mode="drop").reshape(e, cap, d)
    dest_by_flat = jnp.full((t * k,), e * cap, jnp.int32).at[order].set(dest)
    return grouped, dest_by_flat


def _ffn(p, cfg, grouped, dtype):
    act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
    h = act(jnp.einsum("ecd,edf->ecf", grouped,
                       p["wi_gate"].astype(dtype))).astype(dtype)
    h = h * jnp.einsum("ecd,edf->ecf", grouped, p["wi_up"].astype(dtype))
    return jnp.einsum("ecf,efd->ecd", h, p["wo"].astype(dtype))


def moe_apply_sharded(p, cfg, x, *, capacity_factor: float
                      ) -> Tuple[jax.Array, jax.Array]:
    """Drop-in for moe.moe_apply when an activation mesh is installed."""
    mesh, fsdp, tp = current_mesh()
    shape = dict(zip(mesh.axis_names, mesh.devices.shape))
    ax = _fit(mesh, x.shape[0], fsdp)
    b, s, d = x.shape
    e, k = cfg.n_experts, cfg.experts_per_token
    data_axes = ((ax,) if isinstance(ax, str) else tuple(ax)) if ax else ()
    # the shard_map must be FULLY manual (partial-auto mode CHECK-crashes
    # XLA's SPMD partitioner on the transpose); require the batch to
    # divide the whole fsdp product, else fall back to the pjit path
    if data_axes != tuple(fsdp):
        from repro.models import moe as moe_plain
        return moe_plain._moe_apply_dense(p, cfg, x, capacity_factor)
    n_data = 1
    for a in data_axes:
        n_data *= shape[a]
    n_model = shape.get(tp, 1) if tp else 1
    t_loc_all = (b // n_data) * s
    if t_loc_all % max(n_model, 1) != 0:
        from repro.models import moe as moe_plain
        return moe_plain._moe_apply_dense(p, cfg, x, capacity_factor)
    ep = e % n_data == 0 and e >= n_data
    e_loc = e // n_data if ep else e
    t_slice = t_loc_all // max(n_model, 1)
    cap = max(int(capacity_factor * t_slice * k / e), 1)

    def local_fn(p_l, x_l):
        bl = x_l.shape[0]
        xf_all = x_l.reshape(bl * s, d)
        if tp:
            midx = lax.axis_index(tp)
            xf = lax.dynamic_slice_in_dim(xf_all, midx * t_slice, t_slice)
        else:
            xf = xf_all
        gates, eidx, me, fe = _route(p_l, cfg, xf)
        all_axes = data_axes + ((tp,) if tp else ())
        aux = e * jnp.sum(lax.pmean(fe, all_axes) *
                          lax.pmean(me, all_axes))

        grouped, dest_by_flat = _group(xf, eidx, e, cap, k)
        if ep:
            gsh = grouped.reshape(n_data, e_loc, cap, d)
            recv = lax.all_to_all(gsh, data_axes, split_axis=0,
                                  concat_axis=0)   # (n_data, e_loc, cap, d)
            merged = jnp.moveaxis(recv, 0, 1).reshape(
                e_loc, n_data * cap, d)
            yg = _ffn(p_l, cfg, merged, x_l.dtype)
            yg = jnp.moveaxis(yg.reshape(e_loc, n_data, cap, d), 1, 0)
            back = lax.all_to_all(yg, data_axes, split_axis=0,
                                  concat_axis=0)
            yg_flat = back.reshape(e * cap, d)
        else:
            yg_flat = _ffn(p_l, cfg, grouped, x_l.dtype).reshape(
                e * cap, d)

        contrib = jnp.concatenate(
            [yg_flat, jnp.zeros((1, d), yg_flat.dtype)],
            axis=0)[dest_by_flat]
        out = jnp.sum(contrib.reshape(t_slice, k, d) *
                      gates.astype(x_l.dtype)[..., None], axis=1)
        if cfg.n_shared_experts:
            sp = p_l["shared"]
            act = jax.nn.gelu if cfg.act == "gelu" else jax.nn.silu
            hs = act(xf @ sp["wi_gate"].astype(x_l.dtype)).astype(
                x_l.dtype) * (xf @ sp["wi_up"].astype(x_l.dtype))
            out = out + hs @ sp["wo"].astype(x_l.dtype)
        if tp:
            out = lax.all_gather(out, tp, tiled=True)   # (t_loc_all, d)
        return out.reshape(bl, s, d), aux

    e_spec = data_axes if ep else None
    p_specs = {
        "router": P(),
        "wi_gate": P(e_spec, None, None),
        "wi_up": P(e_spec, None, None),
        "wo": P(e_spec, None, None),
    }
    if "shared" in p:
        p_specs["shared"] = {"wi_gate": P(), "wi_up": P(), "wo": P()}
    manual = set(data_axes) | ({tp} if tp else set())
    in_specs = (p_specs, P(data_axes, None, None))
    out_specs = (P(data_axes, None, None), P())
    fn = jax.shard_map(local_fn, mesh=mesh, in_specs=in_specs,
                       out_specs=out_specs, axis_names=manual,
                       check_vma=False)
    return fn(p, x)
