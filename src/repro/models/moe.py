"""Top-k MoE with capacity-bounded dispatch + dense grouped matmuls.

Two execution paths with identical semantics:

- **Local path** (no mesh / single device / tests): scatter-based dispatch in
  plain jnp.

- **Expert-parallel shard_map path** (production meshes): GSPMD cannot shard
  computed-index scatters (it replicates the dispatch buffers — hundreds of
  GB/device at dbrx scale), so on a mesh the whole FFN block runs under
  shard_map: each (data, model) shard routes its *local* tokens, keeps only
  the experts its model-shard owns, all-gathers the layer's expert weights
  over the FSDP ("data") axis in bf16, computes the dense grouped matmul
  locally, and combines with a psum over "model" (the EP-combine; an
  explicit all-to-all would halve this wire cost — see EXPERIMENTS §Perf).

Capacity is per (token-shard × expert) on the mesh path, per (sequence ×
expert) on the local path; overflow drops tokens (the residual connection
carries them).
"""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax import shard_map
from jax.sharding import PartitionSpec as P

from repro.config import ModelConfig, SWIGLU, GEGLU
from repro.models.params import ParamSpec
from repro.models.sharding import _current_mesh, logical_to_pspec


def moe_specs(cfg: ModelConfig) -> dict:
    D, F, E = cfg.d_model, cfg.d_ff, cfg.n_experts
    return {
        "router": ParamSpec((D, E), ("fsdp", None), init="scaled"),
        "wg": ParamSpec((E, D, F), ("expert", "fsdp", None), init="scaled"),
        "wi": ParamSpec((E, D, F), ("expert", "fsdp", None), init="scaled"),
        "wo": ParamSpec((E, F, D), ("expert", None, "fsdp"), init="scaled"),
    }


def _capacity(tokens: int, k: int, n_experts: int, cf: float) -> int:
    return max(int(tokens * k * cf / n_experts) + 1, k)


def _route(cfg: ModelConfig, router, x_flat):
    """x_flat (T, D) -> (weights (T,k), ids (T,k), probs (T,E)).

    bf16 matmul with f32 accumulation: casting x_flat itself to f32 would
    materialize a (T, D) f32 copy (GBs at dbrx scale)."""
    logits = jnp.matmul(x_flat, router.astype(x_flat.dtype),
                        preferred_element_type=jnp.float32)
    probs = jax.nn.softmax(logits, axis=-1)
    weights, ids = jax.lax.top_k(probs, cfg.top_k)
    weights = weights / jnp.maximum(weights.sum(-1, keepdims=True), 1e-9)
    return weights, ids, probs


def _expert_ffn(cfg: ModelConfig, buf, wg, wi, wo, dtype):
    """buf (E, C, D) x weights (E, D, F)/(E, F, D) -> (E, C, D)."""
    if cfg.mlp_variant in (SWIGLU, GEGLU):
        act = jax.nn.silu if cfg.mlp_variant == SWIGLU else jax.nn.gelu
        h = act(jnp.einsum("ecd,edf->ecf", buf, wg)) * jnp.einsum(
            "ecd,edf->ecf", buf, wi)
    else:
        h = jax.nn.gelu(jnp.einsum("ecd,edf->ecf", buf, wi))
    return jnp.einsum("ecf,efd->ecd", h, wo)


def _dispatch_combine_local(cfg, x_flat, ids, weights, e0, n_local, capacity,
                            ffn):
    """Scatter local tokens into per-expert buffers, run ffn, gather back.

    x_flat (T, D); ids/weights (T, k); experts [e0, e0+n_local) are local.
    Returns y (T, D) — contributions of *local* experts only.

    Dispatch/combine iterate over the k routing choices (k is small and
    static) so no (T*k, D) token copy is ever materialized, and every
    intermediate stays in the activation dtype (a single f32 promotion here
    costs GBs/device at dbrx scale).
    """
    T, D = x_flat.shape
    k = cfg.top_k
    dtype = x_flat.dtype
    local = (ids >= e0) & (ids < e0 + n_local)            # (T, k)
    e_loc = jnp.where(local, ids - e0, 0)
    # slot within expert: rank among local assignments (order: k-major)
    oh = jax.nn.one_hot(jnp.where(local, e_loc, n_local), n_local + 1,
                        dtype=jnp.int32).reshape(T * k, n_local + 1)
    slot = jnp.sum((jnp.cumsum(oh, axis=0) - oh) * oh, axis=-1).reshape(T, k)
    keep = local & (slot < capacity)
    slot_c = jnp.minimum(slot, capacity - 1)

    buf = jnp.zeros((n_local, capacity, D), dtype)
    for j in range(k):                                    # no (T*k, D) copies
        contrib = jnp.where(keep[:, j, None], x_flat, 0)
        buf = buf.at[e_loc[:, j], slot_c[:, j]].add(contrib)

    out_buf = ffn(buf)                                    # (n_local, C, D)

    y = jnp.zeros((T, D), dtype)
    for j in range(k):
        w_j = jnp.where(keep[:, j], weights[:, j], 0.0).astype(dtype)
        y = y + out_buf[e_loc[:, j], slot_c[:, j]] * w_j[:, None]
    drop_frac = 1.0 - keep.sum() / jnp.maximum(local.sum(), 1)
    return y, drop_frac


def _moe_mesh_path(cfg: ModelConfig, p: dict, x: jax.Array, mesh) -> tuple:
    B, S, D = x.shape
    E = cfg.n_experts
    dtype = x.dtype
    batch_axes = tuple(a for a in ("pod", "data") if a in mesh.axis_names)
    n_batch = 1
    for a in batch_axes:
        n_batch *= mesh.shape[a]
    n_model = mesh.shape["model"]
    if B % n_batch or E % n_model or D % mesh.shape.get("data", 1):
        return _moe_local_path(cfg, p, x)                 # fall back (smoke)
    E_loc = E // n_model
    T_loc = (B // n_batch) * S
    capacity = _capacity(T_loc, cfg.top_k, E, cfg.capacity_factor)

    x_spec = P(batch_axes if len(batch_axes) > 1 else batch_axes[0], None, None)
    wg_spec = logical_to_pspec(("expert", "fsdp", None), p["wg"].shape, mesh)
    wo_spec = logical_to_pspec(("expert", None, "fsdp"), p["wo"].shape, mesh)

    def inner(x_loc, router, wg, wi, wo):
        Bl, Sl, _ = x_loc.shape
        x_flat = x_loc.reshape(Bl * Sl, D)
        weights, ids, probs = _route(cfg, router, x_flat)

        # aux load-balance loss (global via pmean)
        me = probs.mean(axis=0)
        ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(
            1.0 / (x_flat.shape[0] * cfg.top_k))
        lb = E * jnp.sum(me * ce)
        lb = jax.lax.pmean(lb, batch_axes + ("model",))

        # FSDP: unshard this layer's expert weights over "data" (bf16 wire)
        if "data" in mesh.axis_names and mesh.shape["data"] > 1:
            wg_f = jax.lax.all_gather(wg.astype(dtype), "data", axis=1, tiled=True)
            wi_f = jax.lax.all_gather(wi.astype(dtype), "data", axis=1, tiled=True)
            wo_f = jax.lax.all_gather(wo.astype(dtype), "data", axis=2, tiled=True)
        else:
            wg_f, wi_f, wo_f = (w.astype(dtype) for w in (wg, wi, wo))

        e0 = jax.lax.axis_index("model") * E_loc
        ffn = lambda buf: _expert_ffn(cfg, buf, wg_f, wi_f, wo_f, dtype)
        y, drop = _dispatch_combine_local(cfg, x_flat, ids, weights, e0,
                                          E_loc, capacity, ffn)
        y = jax.lax.psum(y, "model")                      # EP combine
        drop = jax.lax.pmean(drop, batch_axes + ("model",))
        return y.reshape(Bl, Sl, D), lb, drop

    y, lb, drop = shard_map(
        inner, mesh=mesh,
        in_specs=(x_spec, P(None, None), wg_spec, wg_spec, wo_spec),
        out_specs=(x_spec, P(), P()),
        check_vma=False,
    )(x, p["router"], p["wg"], p["wi"], p["wo"])
    return y, {"lb_loss": lb, "router_dropped": drop}


def _moe_local_path(cfg: ModelConfig, p: dict, x: jax.Array) -> tuple:
    B, S, D = x.shape
    E = cfg.n_experts
    dtype = x.dtype
    x_flat = x.reshape(B * S, D)
    weights, ids, probs = _route(cfg, p["router"], x_flat)
    me = probs.mean(axis=0)
    ce = jnp.zeros((E,), jnp.float32).at[ids.reshape(-1)].add(
        1.0 / (B * S * cfg.top_k))
    lb = E * jnp.sum(me * ce)
    capacity = _capacity(B * S, cfg.top_k, E, cfg.capacity_factor)
    ffn = lambda buf: _expert_ffn(cfg, buf, p["wg"].astype(dtype),
                                  p["wi"].astype(dtype), p["wo"].astype(dtype),
                                  dtype)
    y, drop = _dispatch_combine_local(cfg, x_flat, ids, weights, 0, E,
                                      capacity, ffn)
    return y.reshape(B, S, D), {"lb_loss": lb, "router_dropped": drop}


def moe_apply(cfg: ModelConfig, p: dict, x: jax.Array, *,
              group_axis: str = "seq") -> tuple:
    """x: (B, S, D) -> (y (B, S, D), aux metrics). group_axis kept for API
    compatibility; capacity grouping is per token-shard on mesh."""
    del group_axis
    mesh = _current_mesh()
    if mesh is not None and "model" in mesh.axis_names:
        return _moe_mesh_path(cfg, p, x, mesh)
    return _moe_local_path(cfg, p, x)
