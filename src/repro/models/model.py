"""Model assembly for all assigned architecture families.

Families: dense / moe (decoder-only LMs), encdec (whisper backbone),
vlm (pixtral backbone; vision frontend stubbed), ssm (mamba2),
hybrid (zamba2: mamba2 blocks + a shared attention block every N).

Design rules:
  * Layers run under ``jax.lax.scan`` over stacked params — HLO size and
    compile time are O(1) in depth (critical for 61-layer 1T-param dry-runs).
  * Same spec tree drives abstract (ShapeDtypeStruct) and concrete init.
  * All entry points are pure functions: (params, cfg, batch[, cache]) -> out.
"""
from __future__ import annotations

import functools
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models import ssm as ssm_mod
from repro.models.attention import (attention, decode_attention,
                                    paged_decode_attention,
                                    paged_prefill_attention)
from repro.models.layers import mlp, rms_norm, softcap
from repro.models.moe import moe_ffn
from repro.models.params import P, abstract_params, init_params

Params = Dict[str, Any]


# ---------------------------------------------------------------------------
# Parameter specs
# ---------------------------------------------------------------------------

def _stack(specs: Dict[str, P], n: int) -> Dict[str, P]:
    return {k: P((n,) + v.shape, v.init, v.axis, v.scale, v.dtype)
            for k, v in specs.items()}


def _attn_specs(cfg: ModelConfig) -> Dict[str, P]:
    d, hd = cfg.d_model, cfg.head_dim
    s: Dict[str, P] = {
        "ln_w": P((d,), "ones"),
        "wq": P((d, cfg.num_heads * hd)),
        "wk": P((d, cfg.num_kv_heads * hd)),
        "wv": P((d, cfg.num_kv_heads * hd)),
        "wo": P((cfg.num_heads * hd, d)),
    }
    if cfg.use_qk_norm:
        s["q_norm"] = P((hd,), "ones")
        s["k_norm"] = P((hd,), "ones")
    if cfg.use_post_norm:
        s["post_ln_w"] = P((d,), "ones")
    return s


def _mlp_specs(cfg: ModelConfig, d_ff: Optional[int] = None) -> Dict[str, P]:
    d, f = cfg.d_model, d_ff or cfg.d_ff
    s = {
        "ln_w": P((d,), "ones"),
        "wi_gate": P((d, f)),
        "wi_up": P((d, f)),
        "wo": P((f, d)),
    }
    if cfg.use_post_norm:
        s["post_ln_w"] = P((d,), "ones")
    return s


def _moe_specs(cfg: ModelConfig) -> Dict[str, P]:
    d, f, E = cfg.d_model, cfg.moe_d_ff, cfg.num_experts
    s = {
        "ln_w": P((d,), "ones"),
        "w_router": P((d, E), "small", scale=0.02, dtype="float32"),
        "w_gate": P((E, d, f)),
        "w_up": P((E, d, f)),
        "w_down": P((E, f, d), axis=-2),
    }
    return s


def _mamba_specs(cfg: ModelConfig) -> Dict[str, P]:
    dm = ssm_mod.mamba2_dims(cfg)
    d = cfg.d_model
    return {
        "ln_w": P((d,), "ones"),
        "in_proj": P((d, dm["in_dim"])),
        "conv_w": P((cfg.conv_width, dm["conv_ch"]), "small", scale=0.1),
        "conv_b": P((dm["conv_ch"],), "zeros"),
        "dt_bias": P((dm["H"],), "zeros", dtype="float32"),
        "A_log": P((dm["H"],), "ones", dtype="float32"),
        "D": P((dm["H"],), "ones", dtype="float32"),
        "norm_w": P((dm["di"],), "ones"),
        "out_proj": P((dm["di"], d)),
    }


def param_specs(cfg: ModelConfig) -> Params:
    d, V = cfg.d_model, cfg.vocab_size
    specs: Params = {
        "embed": P((V, d), "embed", scale=0.02),
        "final_ln_w": P((d,), "ones"),
    }
    if not cfg.tie_embeddings:
        specs["lm_head"] = P((d, V), "small", scale=0.02)

    if cfg.family in ("dense", "vlm"):
        if cfg.local_global:  # gemma2: scan over (local, global) pairs
            npairs = cfg.num_layers // 2
            specs["local"] = {"attn": _stack(_attn_specs(cfg), npairs),
                              "mlp": _stack(_mlp_specs(cfg), npairs)}
            specs["global"] = {"attn": _stack(_attn_specs(cfg), npairs),
                               "mlp": _stack(_mlp_specs(cfg), npairs)}
        else:
            L = cfg.num_layers
            specs["blocks"] = {"attn": _stack(_attn_specs(cfg), L),
                               "mlp": _stack(_mlp_specs(cfg), L)}
    elif cfg.family == "moe":
        L = cfg.num_layers
        specs["blocks"] = {"attn": _stack(_attn_specs(cfg), L),
                           "moe": _stack(_moe_specs(cfg), L)}
        if cfg.d_ff > 0:  # shared dense expert (kimi-k2)
            specs["blocks"]["shared_mlp"] = _stack(
                _mlp_specs(cfg, cfg.d_ff), L)
    elif cfg.family == "encdec":
        L = cfg.num_layers
        specs["enc_blocks"] = {"attn": _stack(_attn_specs(cfg), L),
                               "mlp": _stack(_mlp_specs(cfg), L)}
        specs["dec_blocks"] = {"self_attn": _stack(_attn_specs(cfg), L),
                               "cross_attn": _stack(_attn_specs(cfg), L),
                               "mlp": _stack(_mlp_specs(cfg), L)}
        specs["enc_final_ln_w"] = P((d,), "ones")
    elif cfg.family == "ssm":
        specs["blocks"] = {"mamba": _stack(_mamba_specs(cfg),
                                           cfg.num_layers)}
    elif cfg.family == "hybrid":
        assert cfg.num_layers % cfg.attn_every == 0
        specs["blocks"] = {"mamba": _stack(_mamba_specs(cfg),
                                           cfg.num_layers)}
        specs["shared"] = {"attn": _attn_specs(cfg),
                           "mlp": _mlp_specs(cfg)}
    else:
        raise ValueError(cfg.family)
    return specs


def abstract(cfg: ModelConfig) -> Params:
    return abstract_params(param_specs(cfg), cfg.param_dtype)


@functools.partial(jax.jit, static_argnums=0)
def init(cfg: ModelConfig, rng) -> Params:
    """Random weights, drawn in one program (eagerly, every leaf shape
    compiles its own, which takes a minute on a TPU at full width)."""
    return init_params(param_specs(cfg), rng, cfg.param_dtype)


# ---------------------------------------------------------------------------
# Blocks
# ---------------------------------------------------------------------------

def _qk_normed(p, cfg, q, k):
    if cfg.use_qk_norm:
        q = rms_norm(q, p["q_norm"], cfg.norm_eps)
        k = rms_norm(k, p["k_norm"], cfg.norm_eps)
    return q, k


def _attn_scale(cfg) -> float:
    dim = getattr(cfg, "attn_scale_dim", 0) or cfg.head_dim
    return float(dim) ** -0.5


def attn_block(p: Params, cfg: ModelConfig, x: jnp.ndarray, *,
               mode: str,                    # train | prefill | decode
               causal: bool = True,
               window: int = 0,
               layer_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               pos: Optional[jnp.ndarray] = None,
               cross_kv: Optional[Tuple[jnp.ndarray, jnp.ndarray]] = None,
               rope: bool = True):
    """Pre-norm attention with residual. Returns (x_out, new_kv | None).

    * train:   full self-attention, new_kv=None
    * prefill: full self-attention, returns (k, v) [B,S,Hkv,hd]
    * decode:  layer_kv is the full cache slice; the new token's k/v is
               written at index ``pos``; returns updated cache slice.
    * cross_kv set -> cross-attention (no rope, non-causal, ignores cache).
    """
    if layer_kv is not None and layer_kv[0].size == 0:
        layer_kv = None  # scan placeholder for cache-less modes
    B, S, _ = x.shape
    hd = cfg.head_dim
    h = rms_norm(x, p["ln_w"], cfg.norm_eps, use_pallas=False)
    q = jnp.einsum("bsd,dk->bsk", h, p["wq"]).reshape(
        B, S, cfg.num_heads, hd)

    new_kv = None
    if cross_kv is not None:
        k, v = cross_kv
        q, k = _qk_normed(p, cfg, q, k)
        out = attention(q, k, v, causal=False, scale=_attn_scale(cfg),
                        attn_softcap=cfg.attn_softcap,
                        use_pallas=cfg.use_pallas,
                        f32_logits=cfg.attn_f32_logits)
    else:
        k = jnp.einsum("bsd,dk->bsk", h, p["wk"]).reshape(
            B, S, cfg.num_kv_heads, hd)
        v = jnp.einsum("bsd,dk->bsk", h, p["wv"]).reshape(
            B, S, cfg.num_kv_heads, hd)
        q, k = _qk_normed(p, cfg, q, k)
        if mode == "decode":
            assert layer_kv is not None and pos is not None and S == 1
            if rope:
                from repro.models.layers import apply_rope
                posv = jnp.asarray(pos, jnp.int32).reshape(1)
                q = apply_rope(q, posv, cfg.rope_theta)
                k = apply_rope(k, posv, cfg.rope_theta)
            ck, cv = layer_kv
            ck = jax.lax.dynamic_update_slice_in_dim(
                ck, k.astype(ck.dtype), pos, axis=1)
            cv = jax.lax.dynamic_update_slice_in_dim(
                cv, v.astype(cv.dtype), pos, axis=1)
            out = decode_attention(
                q, ck, cv, pos, window=window,
                attn_softcap=cfg.attn_softcap, scale=_attn_scale(cfg),
                use_pallas=cfg.use_pallas,
                f32_logits=cfg.attn_f32_logits)
            new_kv = (ck, cv)
        else:
            if rope:
                from repro.models.layers import apply_rope
                posv = jnp.arange(S)
                q = apply_rope(q, posv, cfg.rope_theta)
                k = apply_rope(k, posv, cfg.rope_theta)
            out = attention(q, k, v, causal=causal, window=window,
                            attn_softcap=cfg.attn_softcap,
                            scale=_attn_scale(cfg),
                            use_pallas=cfg.use_pallas,
                            f32_logits=cfg.attn_f32_logits)
            if mode == "prefill":
                new_kv = (k, v)

    out = jnp.einsum("bsk,kd->bsd",
                     out.reshape(B, S, cfg.num_heads * hd), p["wo"])
    if cfg.use_post_norm:
        out = rms_norm(out, p["post_ln_w"], cfg.norm_eps)
    return x + out, new_kv


def mlp_block(p: Params, cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    h = rms_norm(x, p["ln_w"], cfg.norm_eps)
    out = mlp(h, p["wi_gate"], p["wi_up"], p["wo"], cfg.act)
    if cfg.use_post_norm:
        out = rms_norm(out, p["post_ln_w"], cfg.norm_eps)
    return x + out


def moe_block(p: Params, cfg: ModelConfig, x: jnp.ndarray,
              shared_mlp: Optional[Params] = None):
    B, S, d = x.shape
    h = rms_norm(x, p["ln_w"], cfg.norm_eps)
    from repro.models.moe_ep import current_ep_mesh, moe_ffn_ep
    impl = moe_ffn_ep if current_ep_mesh() is not None else moe_ffn
    out = impl(h.reshape(B * S, d), p["w_router"], p["w_gate"],
               p["w_up"], p["w_down"], k=cfg.experts_per_token,
               capacity_factor=cfg.capacity_factor, act=cfg.act)
    y = out.y.reshape(B, S, d)
    if shared_mlp is not None:
        hs = rms_norm(x, shared_mlp["ln_w"], cfg.norm_eps)
        y = y + mlp(hs, shared_mlp["wi_gate"], shared_mlp["wi_up"],
                    shared_mlp["wo"], cfg.act)
    return x + y, out.aux_loss


def mamba_block(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                state: Optional[ssm_mod.SSMState] = None, *,
                decode: bool = False):
    h = rms_norm(x, p["ln_w"], cfg.norm_eps)
    y, new_state = ssm_mod.mamba2_block(p, cfg, h, state, decode=decode)
    return x + y, new_state


# ---------------------------------------------------------------------------
# Scan-over-layers drivers
# ---------------------------------------------------------------------------

def _maybe_remat(fn, cfg, mode):
    if mode != "train" or cfg.remat == "none":
        return fn
    if cfg.remat == "dots":
        return jax.checkpoint(
            fn, policy=jax.checkpoint_policies.dots_saveable)
    return jax.checkpoint(fn)


def _scan(body, carry, xs, cfg, mode):
    return jax.lax.scan(_maybe_remat(body, cfg, mode), carry, xs)


# ---------------------------------------------------------------------------
# Forward passes per family
# ---------------------------------------------------------------------------

@jax.named_scope("embed")
def _embed(params, cfg, tokens):
    x = params["embed"][tokens]  # gather [B,S,d]
    if getattr(cfg, "embed_scale", False) or cfg.local_global:
        x = x * jnp.asarray(cfg.d_model ** 0.5, x.dtype)
    return x


@jax.named_scope("head")
def _unembed(params, cfg, h):
    """Final norm + LM head (+ gemma2 final softcap). h: [..., d]."""
    h = rms_norm(h, params["final_ln_w"], cfg.norm_eps)
    w = (params["embed"].T if cfg.tie_embeddings else params["lm_head"])
    logits = jnp.einsum("...d,dv->...v", h, w,
                        preferred_element_type=jnp.float32)
    if cfg.final_softcap > 0:
        logits = softcap(logits, cfg.final_softcap)
    return logits


def _dense_stack(params, cfg, x, mode, cache=None):
    """Dense / vlm decoder stack. Returns (h, new_cache_kv, aux)."""
    aux = jnp.zeros((), jnp.float32)
    pos = None if cache is None else cache["len"]

    if cfg.local_global:
        def body(h, xs):
            (pl, pg, kvl, kvg) = xs
            h, nkvl = attn_block(pl["attn"], cfg, h, mode=mode,
                                 window=cfg.sliding_window, layer_kv=kvl,
                                 pos=pos)
            h = mlp_block(pl["mlp"], cfg, h)
            h, nkvg = attn_block(pg["attn"], cfg, h, mode=mode,
                                 layer_kv=kvg, pos=pos)
            h = mlp_block(pg["mlp"], cfg, h)
            return h, (nkvl, nkvg)

        kvl = (cache["local_k"], cache["local_v"]) if cache else None
        kvg = (cache["global_k"], cache["global_v"]) if cache else None
        npairs = cfg.num_layers // 2
        xs = (params["local"], params["global"],
              _split_kv(kvl, npairs), _split_kv(kvg, npairs))
        x, (nkvl, nkvg) = _scan(body, x, xs, cfg, mode)
        new_kv = _merge_local_global(nkvl, nkvg, mode)
        return x, new_kv, aux

    def body(h, xs):
        (pb, kv) = xs
        h, nkv = attn_block(pb["attn"], cfg, h, mode=mode, layer_kv=kv,
                            pos=pos)
        if "moe" in pb:
            h, a = moe_block(pb["moe"], cfg, h, pb.get("shared_mlp"))
        else:
            h = mlp_block(pb["mlp"], cfg, h)
            a = jnp.zeros((), jnp.float32)
        return h, (nkv, a)

    kv = (cache["k"], cache["v"]) if cache else None
    xs = (params["blocks"], _split_kv(kv, cfg.num_layers))
    x, (nkv, auxs) = _scan(body, x, xs, cfg, mode)
    new_cache = None if mode == "train" else {"k": nkv[0], "v": nkv[1]}
    return x, new_cache, jnp.sum(auxs)


def _split_kv(kv, n):
    """Cache arrays already have leading L dim -> scan consumes them as xs.
    When no cache, feed size-0 placeholders (scan needs a pytree with
    leading dim n); attn_block treats size-0 kv as None."""
    if kv is None:
        return (jnp.zeros((n, 0)), jnp.zeros((n, 0)))
    return kv


def _merge_local_global(nkvl, nkvg, mode):
    if mode == "train":
        return None
    return {"local_k": nkvl[0], "local_v": nkvl[1],
            "global_k": nkvg[0], "global_v": nkvg[1]}


def _ssm_stack(params, cfg, x, mode, cache=None):
    """Pure-mamba stack. cache: {"ssm": [L,B,H,P,N], "conv": [L,B,W-1,ch]}."""
    decode = mode == "decode"

    def body(h, xs):
        pb, st = xs
        state = (ssm_mod.SSMState(ssm=st[0], conv=st[1])
                 if st is not None and st[0].ndim > 2 else None)
        h, ns = mamba_block(pb["mamba"], cfg, h, state, decode=decode)
        out = ((ns.ssm, ns.conv) if ns is not None
               else (jnp.zeros((0,)), jnp.zeros((0,))))
        return h, out

    st = ((cache["ssm"], cache["conv"]) if cache is not None
          else (jnp.zeros((cfg.num_layers, 0, 0)),
                jnp.zeros((cfg.num_layers, 0, 0))))
    x, (nssm, nconv) = _scan(body, x, (params["blocks"], st), cfg, mode)
    new_cache = None
    if cache is not None:
        new_cache = {"ssm": nssm, "conv": nconv}
    return x, new_cache, jnp.zeros((), jnp.float32)


def _hybrid_stack(params, cfg, x, mode, cache=None):
    """Zamba2: groups of ``attn_every`` mamba blocks, a single *shared*
    attention+MLP block applied before each group (per-application KV)."""
    n_apps = cfg.num_layers // cfg.attn_every
    per = cfg.attn_every
    decode = mode == "decode"
    pos = None if cache is None else cache["len"]
    shared = params["shared"]

    def group_body(h, xs):
        mamba_group, st_group, kv = xs
        h, nkv = attn_block(shared["attn"], cfg, h, mode=mode, layer_kv=kv,
                            pos=pos)
        h = mlp_block(shared["mlp"], cfg, h)

        def inner(hh, inner_xs):
            pb, st = inner_xs
            state = (ssm_mod.SSMState(ssm=st[0], conv=st[1])
                     if st is not None and st[0].ndim > 2 else None)
            hh, ns = mamba_block(pb, cfg, hh, state, decode=decode)
            out = ((ns.ssm, ns.conv) if ns is not None
                   else (jnp.zeros((0,)), jnp.zeros((0,))))
            return hh, out

        h, nst = jax.lax.scan(_maybe_remat(inner, cfg, mode), h,
                              (mamba_group, st_group))
        nkv_out = nkv if nkv is not None else (jnp.zeros((0,)),) * 2
        return h, (nst, nkv_out)

    mb = params["blocks"]["mamba"]
    mamba_grouped = jax.tree.map(
        lambda a: a.reshape((n_apps, per) + a.shape[1:]), mb)
    if cache is not None:
        st = (cache["ssm"].reshape((n_apps, per) + cache["ssm"].shape[1:]),
              cache["conv"].reshape((n_apps, per) + cache["conv"].shape[1:]))
        kv = (cache["k"], cache["v"])  # [n_apps, B, S, Hkv, hd]
    else:
        st = (jnp.zeros((n_apps, per, 0)), jnp.zeros((n_apps, per, 0)))
        kv = (jnp.zeros((n_apps, 0)), jnp.zeros((n_apps, 0)))

    x, (nst, nkv) = _scan(group_body, x, (mamba_grouped, st, kv), cfg, mode)
    new_cache = None
    if cache is not None:
        L = cfg.num_layers
        new_cache = {
            "ssm": nst[0].reshape((L,) + nst[0].shape[2:]),
            "conv": nst[1].reshape((L,) + nst[1].shape[2:]),
            "k": nkv[0], "v": nkv[1],
        }
    return x, new_cache, jnp.zeros((), jnp.float32)


def _encdec_stacks(params, cfg, enc_x, dec_x, mode, cache=None):
    """Whisper backbone. enc_x: [B,S_enc,d] embeddings (frontend stub);
    dec_x: [B,S_dec,d] decoder token embeddings."""
    pos = None if cache is None else cache["len"]

    if enc_x is not None:
        def enc_body(h, pb):
            h, _ = attn_block(pb["attn"], cfg, h, mode="train", causal=False,
                              rope=False)
            h = mlp_block(pb["mlp"], cfg, h)
            return h, None
        enc_h, _ = _scan(enc_body, enc_x, params["enc_blocks"], cfg, mode)
        enc_h = rms_norm(enc_h, params["enc_final_ln_w"], cfg.norm_eps)

        def cross_kv_body(_, pb):
            k = jnp.einsum("bsd,dk->bsk", enc_h, pb["cross_attn"]["wk"])
            v = jnp.einsum("bsd,dk->bsk", enc_h, pb["cross_attn"]["wv"])
            B, S, _ = enc_h.shape
            k = k.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            v = v.reshape(B, S, cfg.num_kv_heads, cfg.head_dim)
            return None, (k, v)
        _, cross = jax.lax.scan(cross_kv_body, None, params["dec_blocks"])
    else:
        cross = (cache["cross_k"], cache["cross_v"])

    def dec_body(h, xs):
        pb, kv, ckv = xs
        h, nkv = attn_block(pb["self_attn"], cfg, h, mode=mode, layer_kv=kv,
                            pos=pos)
        h, _ = attn_block(pb["cross_attn"], cfg, h, mode="train",
                          cross_kv=ckv, rope=False)
        h = mlp_block(pb["mlp"], cfg, h)
        return h, nkv if nkv is not None else (jnp.zeros((0,)),) * 2

    kv = (cache["k"], cache["v"]) if cache else None
    xs = (params["dec_blocks"], _split_kv(kv, cfg.num_layers), cross)
    dec_h, nkv = _scan(dec_body, dec_x, xs, cfg, mode)
    new_cache = None
    if mode != "train":
        new_cache = {"k": nkv[0], "v": nkv[1],
                     "cross_k": cross[0], "cross_v": cross[1]}
    return dec_h, new_cache, jnp.zeros((), jnp.float32)


# ---------------------------------------------------------------------------
# Public entry points
# ---------------------------------------------------------------------------

def forward_train(params: Params, cfg: ModelConfig, batch: Dict[str, Any]):
    """Returns (hidden [B,S,d], aux_loss scalar). Loss lives in train/loss.py."""
    if cfg.family == "encdec":
        enc_x = batch["enc_embeds"].astype(jnp.dtype(cfg.compute_dtype))
        dec_x = _embed(params, cfg, batch["tokens"])
        h, _, aux = _encdec_stacks(params, cfg, enc_x, dec_x, "train")
        return h, aux
    x = _embed(params, cfg, batch["tokens"])
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    stack = {"dense": _dense_stack, "moe": _dense_stack, "vlm": _dense_stack,
             "ssm": _ssm_stack, "hybrid": _hybrid_stack}[cfg.family]
    h, _, aux = stack(params, cfg, x, "train")
    return h, aux


def lm_logits(params: Params, cfg: ModelConfig, hidden: jnp.ndarray):
    return _unembed(params, cfg, hidden)


def init_cache(cfg: ModelConfig, batch: int, max_len: int,
               abstract_only: bool = False,
               cross_len: int = 1500):
    """KV/SSM cache pytree (concrete zeros or ShapeDtypeStructs)."""
    dt = jnp.dtype(cfg.compute_dtype)
    mk = (jax.ShapeDtypeStruct if abstract_only
          else lambda s, d: jnp.zeros(s, d))
    hd, Hkv = cfg.head_dim, cfg.num_kv_heads
    cache: Dict[str, Any] = {"len": mk((), jnp.int32)}
    if cfg.family in ("dense", "vlm", "moe"):
        if cfg.local_global:
            npairs = cfg.num_layers // 2
            for pre in ("local", "global"):
                cache[f"{pre}_k"] = mk((npairs, batch, max_len, Hkv, hd), dt)
                cache[f"{pre}_v"] = mk((npairs, batch, max_len, Hkv, hd), dt)
        else:
            L = cfg.num_layers
            cache["k"] = mk((L, batch, max_len, Hkv, hd), dt)
            cache["v"] = mk((L, batch, max_len, Hkv, hd), dt)
    elif cfg.family == "encdec":
        L = cfg.num_layers
        cache["k"] = mk((L, batch, max_len, Hkv, hd), dt)
        cache["v"] = mk((L, batch, max_len, Hkv, hd), dt)
        cache["cross_k"] = mk((L, batch, cross_len, Hkv, hd), dt)
        cache["cross_v"] = mk((L, batch, cross_len, Hkv, hd), dt)
    elif cfg.family == "ssm":
        dm = ssm_mod.mamba2_dims(cfg)
        L = cfg.num_layers
        cache["ssm"] = mk((L, batch, dm["H"], dm["P"], dm["N"]), jnp.float32)
        cache["conv"] = mk((L, batch, cfg.conv_width - 1, dm["conv_ch"]), dt)
    elif cfg.family == "hybrid":
        dm = ssm_mod.mamba2_dims(cfg)
        L, n_apps = cfg.num_layers, cfg.num_layers // cfg.attn_every
        cache["ssm"] = mk((L, batch, dm["H"], dm["P"], dm["N"]), jnp.float32)
        cache["conv"] = mk((L, batch, cfg.conv_width - 1, dm["conv_ch"]), dt)
        cache["k"] = mk((n_apps, batch, max_len, Hkv, hd), dt)
        cache["v"] = mk((n_apps, batch, max_len, Hkv, hd), dt)
    return cache


def init_paged_cache(cfg: ModelConfig, batch: int, num_pages: int,
                     page_size: int, abstract_only: bool = False,
                     max_pages: Optional[int] = None):
    """Page-pool KV cache: a shared pool of fixed-size token pages plus a
    per-request page table and length.  Page 0 is the scratch page —
    unused table slots (and padding rows) point at it, so every gather
    hits a valid page and garbage writes land harmlessly.

    Layout: {"lens": [B], "table": [B, maxp], "k"/"v": [L, P, page, Hkv,
    hd]} where maxp = ``max_pages``, the most pages any one request may
    hold (default: the whole usable pool, num_pages - 1).  The decode
    kernel prefetches the table into scalar memory, so its width is sized
    by the longest request, not by the pool.
    """
    if cfg.family not in ("dense", "moe", "vlm") or cfg.local_global:
        raise NotImplementedError(
            f"paged KV cache supports dense-stack families, got "
            f"{cfg.family} (local_global={cfg.local_global})")
    dt = jnp.dtype(cfg.compute_dtype)
    mk = (jax.ShapeDtypeStruct if abstract_only
          else lambda s, d: jnp.zeros(s, d))
    L, hd, Hkv = cfg.num_layers, cfg.head_dim, cfg.num_kv_heads
    maxp = max_pages or max(num_pages - 1, 1)
    return {
        "lens": mk((batch,), jnp.int32),
        "table": mk((batch, maxp), jnp.int32),
        "k": mk((L, num_pages, page_size, Hkv, hd), dt),
        "v": mk((L, num_pages, page_size, Hkv, hd), dt),
    }


def _paged_kv_write(pool, new, table, positions, page_size):
    """Scatter per-token k/v into the page pool.

    pool: [P, page, Hkv, hd]; new: [B, S, Hkv, hd]; positions: [B, S]
    absolute token positions; table: [B, maxp].  Rows whose position
    maps to the scratch page (id 0) overwrite garbage only.
    """
    pids = jnp.take_along_axis(table, positions // page_size, axis=1)
    offs = positions % page_size
    return pool.at[pids, offs].set(new.astype(pool.dtype))


def _paged_attn_block(p: Params, cfg: ModelConfig, x: jnp.ndarray,
                      pools, table, write_table, positions, kv_lens, *,
                      chunk_attend: bool, use_pallas: Optional[bool]):
    """Pre-norm attention with residual over the page pool.

    x: [B, S, d]; positions: [B, S] absolute positions of these tokens;
    kv_lens: [B] total valid tokens after this write.  KV writes route
    through ``write_table`` (inactive rows' tables are zeroed there, so
    their writes land on the scratch page); reads use the real
    ``table``.  With ``chunk_attend`` the S chunk tokens attend causally
    through the row's pages (prefill chunks); otherwise S == 1 decode.
    ``use_pallas`` picks either attention (None: the kernel wherever it
    compiles — see :func:`paged_prefill_attention` and
    :func:`paged_decode_attention`).
    """
    from repro.models.layers import apply_rope
    B, S, _ = x.shape
    hd = cfg.head_dim
    with jax.named_scope("qkv"):
        h = rms_norm(x, p["ln_w"], cfg.norm_eps, use_pallas=False)
        q = jnp.einsum("bsd,dk->bsk", h, p["wq"]).reshape(
            B, S, cfg.num_heads, hd)
        k = jnp.einsum("bsd,dk->bsk", h, p["wk"]).reshape(
            B, S, cfg.num_kv_heads, hd)
        v = jnp.einsum("bsd,dk->bsk", h, p["wv"]).reshape(
            B, S, cfg.num_kv_heads, hd)
        q, k = _qk_normed(p, cfg, q, k)
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    page = pools[0].shape[1]
    with jax.named_scope("kv_write"):
        kp = _paged_kv_write(pools[0], k, write_table, positions, page)
        vp = _paged_kv_write(pools[1], v, write_table, positions, page)
    with jax.named_scope("attention"):
        if chunk_attend:
            out = paged_prefill_attention(
                q, kp, vp, table, positions[:, 0], kv_lens,
                attn_softcap=cfg.attn_softcap, scale=_attn_scale(cfg),
                use_pallas=use_pallas, f32_logits=cfg.attn_f32_logits)
        else:
            out = paged_decode_attention(
                q, kp, vp, table, kv_lens,
                attn_softcap=cfg.attn_softcap, scale=_attn_scale(cfg),
                use_pallas=use_pallas, f32_logits=cfg.attn_f32_logits)
        out = jnp.einsum("bsk,kd->bsd",
                         out.reshape(B, S, cfg.num_heads * hd), p["wo"])
        if cfg.use_post_norm:
            out = rms_norm(out, p["post_ln_w"], cfg.norm_eps)
    return x + out, (kp, vp)


def _paged_stack(params, cfg, x, cache, positions, kv_lens, active, *,
                 chunk_attend: bool, use_pallas: Optional[bool] = None):
    """Dense/moe/vlm stack over the page pool; pools ride scan xs just
    like the dense cache's [L, B, ...] arrays ride theirs."""
    table = cache["table"]
    if active is None:
        write_table = table
    else:
        write_table = jnp.where(jnp.asarray(active, bool)[:, None],
                                table, 0)

    def body(h, xs):
        pb, pools = xs
        h, npools = _paged_attn_block(
            pb["attn"], cfg, h, pools, table, write_table, positions,
            kv_lens, chunk_attend=chunk_attend, use_pallas=use_pallas)
        with jax.named_scope("mlp"):
            if "moe" in pb:
                h, _ = moe_block(pb["moe"], cfg, h, pb.get("shared_mlp"))
            else:
                h = mlp_block(pb["mlp"], cfg, h)
        return h, npools

    xs = (params["blocks"], (cache["k"], cache["v"]))
    x, (nk, nv) = _scan(body, x, xs, cfg, "decode")
    return x, {"k": nk, "v": nv, "table": table}


def decode_step_paged(params: Params, cfg: ModelConfig, cache,
                      token: jnp.ndarray, active=None,
                      use_pallas: Optional[bool] = None):
    """One-token decode over the paged cache; every row is at its own
    position ``lens[b]``.  token: [B, 1] int32; active: optional [B]
    bool — inactive rows (mid-prefill / padding) write to the scratch
    page, keep their length, and produce garbage logits callers must
    not read; use_pallas: the attention path (None: the paged kernel
    where it compiles, the XLA gather elsewhere).  Returns (logits
    [B, 1, V], updated cache)."""
    x = _embed(params, cfg, token)
    positions = cache["lens"][:, None]          # [B, 1]
    h, nc = _paged_stack(params, cfg, x, cache, positions,
                         cache["lens"] + 1, active, chunk_attend=False,
                         use_pallas=use_pallas)
    nl = cache["lens"] + 1
    if active is not None:
        nl = jnp.where(jnp.asarray(active, bool), nl, cache["lens"])
    nc["lens"] = nl
    return _unembed(params, cfg, h), nc


def prefill_chunk(params: Params, cfg: ModelConfig, cache,
                  tokens: jnp.ndarray, start: jnp.ndarray,
                  chunk_lens: jnp.ndarray, active=None,
                  use_pallas: Optional[bool] = None):
    """Process one prompt chunk per row, writing KV into the rows' pages.

    tokens: [B, C] int32 (PAD-filled past each row's chunk); start: [B]
    int32 absolute position of each row's first chunk token;
    chunk_lens: [B] int32 valid tokens this chunk (<= C; short final
    chunks PAD-fill the tail — those writes land beyond the row's
    length inside its own pages, masked now and overwritten by the next
    chunk or decode); active: optional [B] bool — inactive rows
    (decoding / idle) write to the scratch page and keep their length;
    use_pallas: the attention path (None: the paged prefill kernel where
    it compiles, the XLA gather elsewhere).
    Returns (logits at each row's last valid chunk token [B, 1, V],
    cache with lens = start + chunk_lens for active rows).
    """
    x = _embed(params, cfg, tokens)
    C = tokens.shape[1]
    start = jnp.asarray(start, jnp.int32)
    chunk_lens = jnp.asarray(chunk_lens, jnp.int32)
    positions = start[:, None] + jnp.arange(C, dtype=jnp.int32)[None, :]
    h, nc = _paged_stack(params, cfg, x, cache, positions,
                         start + chunk_lens, active, chunk_attend=True,
                         use_pallas=use_pallas)
    nl = start + chunk_lens
    if active is not None:
        nl = jnp.where(jnp.asarray(active, bool), nl, cache["lens"])
    nc["lens"] = nl
    last = jnp.take_along_axis(
        h, jnp.maximum(chunk_lens - 1, 0)[:, None, None], axis=1)
    return _unembed(params, cfg, last), nc


def decode_step(params: Params, cfg: ModelConfig, cache, token: jnp.ndarray):
    """One-token decode. token: [B, 1] int32. Returns (logits [B,1,V], cache)."""
    x = _embed(params, cfg, token)
    stack = {"dense": _dense_stack, "moe": _dense_stack, "vlm": _dense_stack,
             "ssm": _ssm_stack, "hybrid": _hybrid_stack}.get(cfg.family)
    if cfg.family == "encdec":
        h, nc, _ = _encdec_stacks(params, cfg, None, x, "decode", cache)
    else:
        h, nc, _ = stack(params, cfg, x, "decode", cache)
    nc["len"] = cache["len"] + 1
    # carry across non-updated fields (e.g. hybrids update everything already)
    for key in cache:
        if key not in nc:
            nc[key] = cache[key]
    return _unembed(params, cfg, h), nc


def prefill(params: Params, cfg: ModelConfig, batch: Dict[str, Any],
            max_len: int):
    """Process a prompt, build the cache. Returns (last_logits [B,1,V], cache)."""
    if cfg.family == "encdec":
        enc_x = batch["enc_embeds"].astype(jnp.dtype(cfg.compute_dtype))
        dec_x = _embed(params, cfg, batch["tokens"])
        B, S = batch["tokens"].shape[:2]
        h, nc, _ = _encdec_stacks(params, cfg, enc_x, dec_x, "prefill", None)
        nc = _pad_kv_cache(nc, max_len, S)
        nc["len"] = jnp.asarray(S, jnp.int32)
        return _unembed(params, cfg, h[:, -1:]), nc

    x = _embed(params, cfg, batch["tokens"])
    if cfg.family == "vlm":
        pe = batch["patch_embeds"].astype(x.dtype)
        x = jnp.concatenate([pe, x], axis=1)
    S = x.shape[1]
    if cfg.family in ("ssm", "hybrid"):
        # SSM prefill needs real state carry: run with a concrete zero cache
        cache = init_cache(cfg, x.shape[0], max_len)
        stack = _ssm_stack if cfg.family == "ssm" else _hybrid_stack
        h, nc, _ = stack(params, cfg, x, "prefill", cache)
        nc = _pad_kv_cache(nc, max_len, S)
        nc["len"] = jnp.asarray(S, jnp.int32)
        return _unembed(params, cfg, h[:, -1:]), nc

    stack = _dense_stack
    h, nc, _ = stack(params, cfg, x, "prefill", None)
    nc = _pad_kv_cache(nc, max_len, S)
    nc["len"] = jnp.asarray(S, jnp.int32)
    return _unembed(params, cfg, h[:, -1:]), nc


def _pad_kv_cache(nc, max_len: int, cur_len: int):
    """Pad prefill-produced [.., S, Hkv, hd] KV arrays out to max_len slots."""
    def pad(x):
        if x.ndim >= 4 and x.shape[-3] == cur_len and max_len > cur_len:
            pad_width = [(0, 0)] * x.ndim
            pad_width[-3] = (0, max_len - cur_len)
            return jnp.pad(x, pad_width)
        return x
    return {k: (pad(v) if k.endswith(("k", "v")) and "cross" not in k
                and not k.startswith(("ssm", "conv")) else v)
            for k, v in nc.items()}
