"""shard_map expert-parallel MoE (the optimized path).

The GSPMD lowering of the capacity-buffer MoE scatters data-sharded tokens
into an expert-sharded [E, C, d] buffer — XLA's fallback materializes the
FULL buffer per shard and all-reduces it (measured: 24.3 TB of all-reduce
per device per step on qwen3-moe train_4k). This module replaces the
dispatch with the canonical EP pattern:

  local top-k routing -> local capacity buffer [E, C_src, d]
  all_to_all over the EP ('data') axis  (the irreducible token exchange)
  local expert GEMMs with the LOCAL expert shard (TP over 'model' inside)
  reverse all_to_all -> local combine

Capacity semantics change slightly (per-source-shard capacity instead of
global), which is standard for EP implementations.

The mesh is provided via ``ep_mesh_context`` (the launcher/dry-run sets
it); without a context the dense-GSPMD path in ``repro.models.moe`` runs.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Optional, Tuple

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.models.layers import activation
from repro.models.moe import MoEOutput, load_balance_loss, router_topk

_ctx = threading.local()


@contextmanager
def ep_mesh_context(mesh, data_axis: str = "data",
                    model_axis: str = "model",
                    extra_batch_axes: Tuple[str, ...] = (),
                    tp_dispatch: bool = False):
    """Declare the mesh for shard_map MoE. ``extra_batch_axes`` are axes
    tokens are also sharded over but experts are replicated over ('pod').

    ``tp_dispatch``: also shard the routing/dispatch phase over the model
    axis (otherwise every TP rank repeats it on the full local token set —
    measured 9.4 GB/layer of capacity buffer on kimi-k2). Costs one
    all-gather of the received expert inputs before the GEMMs."""
    prev = getattr(_ctx, "info", None)
    _ctx.info = (mesh, data_axis, model_axis, tuple(extra_batch_axes),
                 tp_dispatch)
    try:
        yield
    finally:
        _ctx.info = prev


def current_ep_mesh():
    return getattr(_ctx, "info", None)


def _local_dispatch(x, weights, idx, E: int, C: int):
    """Group local tokens by expert into [E, C, d] (all local ops).

    Returns (buf, tok, slot, sorted_e, wgt, keep)."""
    N, d = x.shape
    k = idx.shape[1]
    flat_e = idx.reshape(-1)
    flat_t = jnp.repeat(jnp.arange(N, dtype=jnp.int32), k)
    flat_w = weights.reshape(-1)
    order = jnp.argsort(flat_e, stable=True)
    sorted_e = flat_e[order]
    counts = jnp.zeros((E,), jnp.int32).at[flat_e].add(1)
    starts = jnp.cumsum(counts) - counts
    pos_in_e = jnp.arange(N * k, dtype=jnp.int32) - starts[sorted_e]
    keep = pos_in_e < C
    tok = flat_t[order]
    wgt = jnp.where(keep, flat_w[order], 0.0)
    slot = jnp.where(keep, pos_in_e, C - 1)
    buf = jnp.zeros((E, C, d), x.dtype)
    buf = buf.at[sorted_e, slot].set(
        jnp.where(keep[:, None], x[tok], 0).astype(x.dtype), mode="drop")
    return buf, tok, slot, sorted_e, wgt, keep


def moe_ffn_ep(
    x: jnp.ndarray,          # [N, d] GLOBAL flattened tokens
    w_router: jnp.ndarray,   # [d, E] replicated
    w_gate: jnp.ndarray,     # [E, d, f] sharded P(data, None, model)
    w_up: jnp.ndarray,
    w_down: jnp.ndarray,     # [E, f, d] sharded P(data, model, None)
    *,
    k: int,
    capacity_factor: float,
    act: str = "silu",
) -> MoEOutput:
    info = current_ep_mesh()
    assert info is not None, "moe_ffn_ep requires ep_mesh_context"
    mesh, daxis, maxis, extra, tp_dispatch = info
    D = mesh.shape[daxis]
    E = w_router.shape[1]
    assert E % D == 0, (E, D)

    token_axes = (extra + (daxis,)) if extra else (daxis,)
    if tp_dispatch:
        token_axes = token_axes + (maxis,)

    def body(xl, wr, wg, wu, wd):
        # xl: [N_local, d]; wg: [E/D, d, f/M]; wd: [E/D, f/M, d]
        Nl, d = xl.shape
        C = max(int(Nl * k * capacity_factor / E), 1)
        C = -(-C // 8) * 8
        logits = jnp.einsum("nd,de->ne", xl, wr,
                            preferred_element_type=jnp.float32)
        probs = jax.nn.softmax(logits, axis=-1)
        weights, idx = router_topk(logits, k)
        aux = load_balance_loss(probs, idx, E)
        aux = jax.lax.pmean(aux, token_axes)
        dropped = jnp.zeros((), jnp.float32)

        buf, tok, slot, sorted_e, wgt, keep = _local_dispatch(
            xl, weights, idx, E, C)
        # exchange: [E, C, d] -> [E/D, D*C, d] (expert-major blocks land
        # on their owning shard)
        recv = jax.lax.all_to_all(buf, daxis, split_axis=0, concat_axis=1,
                                  tiled=True)
        if tp_dispatch:
            # dispatch ran on model-sharded tokens; the expert GEMMs (TP
            # over f) need every token of their experts: gather over TP
            recv = jax.lax.all_gather(recv, maxis, axis=1, tiled=True)
        # local expert GEMMs (TP over 'model' on f)
        g = activation(jnp.einsum("ecd,edf->ecf", recv, wg), act)
        u = jnp.einsum("ecd,edf->ecf", recv, wu)
        y_part = jnp.einsum("ecf,efd->ecd", (g * u).astype(recv.dtype), wd)
        if tp_dispatch:
            # return each TP rank its own token block, summing partials:
            # reduce-scatter == psum + slice at a quarter of the bytes
            y_recv = jax.lax.psum_scatter(y_part, maxis, scatter_dimension=1,
                                          tiled=True)
        else:
            y_recv = jax.lax.psum(y_part, maxis)  # TP partial-sum over f
        # reverse exchange: [E/D, D*C, d] -> [E, C, d]
        y_buf = jax.lax.all_to_all(y_recv.astype(xl.dtype), daxis,
                                   split_axis=1, concat_axis=0, tiled=True)
        y_slots = y_buf[sorted_e, slot]
        y = jnp.zeros((Nl, d), jnp.float32).at[tok].add(
            y_slots.astype(jnp.float32) * wgt[:, None], mode="drop")
        return y.astype(xl.dtype), aux, dropped

    n_spec = P(token_axes if len(token_axes) > 1 else token_axes[0], None)
    out = jax.shard_map(
        body, mesh=mesh,
        in_specs=(n_spec, P(None, None), P(daxis, None, maxis),
                  P(daxis, None, maxis), P(daxis, maxis, None)),
        out_specs=(n_spec, P(), P()),
        check_vma=False,
    )(x, w_router, w_gate, w_up, w_down)
    return MoEOutput(*out)
