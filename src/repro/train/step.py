"""Step builders: train_step / prefill_step / decode_step.

Pure function factories — the returned callables close over static configs
only, so they jit/lower cleanly with pjit shardings for the dry-run and
the real drivers alike.
"""
from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, TrainConfig
from repro.models import model as model_lib
from repro.train import compression as comp
from repro.train import optim
from repro.train.loss import lm_loss


def build_loss_fn(cfg: ModelConfig, seq_chunks: int = 1) -> Callable:
    def loss_fn(params, batch):
        hidden, aux = model_lib.forward_train(params, cfg, batch)
        loss, metrics = lm_loss(params, cfg, hidden, batch["labels"],
                                batch.get("loss_mask"),
                                seq_chunks=seq_chunks)
        total = loss + cfg.router_aux_weight * aux
        metrics = dict(metrics, aux_loss=aux, total_loss=total)
        return total, metrics
    return loss_fn


def build_train_step(cfg: ModelConfig, tc: TrainConfig,
                     seq_chunks: int = 1) -> Callable:
    """(params, opt_state, batch) -> (params, opt_state, metrics).

    With tc.microbatch set, the global batch is split into
    B/microbatch accumulation steps via lax.scan (remat-friendly).
    With tc.grad_compression == 'int8_ef', opt_state carries an error
    buffer inside metrics-free aux (see build_train_step_compressed).
    """
    loss_fn = build_loss_fn(cfg, seq_chunks)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def compute_grads(params, batch):
        if tc.microbatch:
            B = batch["tokens"].shape[0]
            n = B // tc.microbatch
            assert n * tc.microbatch == B, (B, tc.microbatch)
            reshaped = jax.tree.map(
                lambda x: x.reshape((n, tc.microbatch) + x.shape[1:]), batch)

            def body(acc, mb):
                (l, m), g = grad_fn(params, mb)
                g_acc, l_acc = acc
                g_acc = jax.tree.map(
                    lambda a, b: a + b.astype(jnp.float32), g_acc, g)
                return (g_acc, l_acc + l), m

            zero_g = jax.tree.map(
                lambda p: jnp.zeros(p.shape, jnp.float32), params)
            (g_sum, l_sum), ms = jax.lax.scan(
                body, (zero_g, jnp.zeros((), jnp.float32)), reshaped)
            grads = jax.tree.map(lambda g: g / n, g_sum)
            metrics = jax.tree.map(lambda x: jnp.mean(x, axis=0), ms)
            metrics["total_loss"] = l_sum / n
            return grads, metrics
        (_, metrics), grads = grad_fn(params, batch)
        return grads, metrics

    def train_step(params, opt_state, batch):
        grads, metrics = compute_grads(params, batch)
        params, opt_state, opt_metrics = optim.adamw_update(
            params, grads, opt_state, tc)
        return params, opt_state, dict(metrics, **opt_metrics)

    return train_step


def build_train_step_compressed(cfg: ModelConfig, tc: TrainConfig) -> Callable:
    """Variant with int8 error-feedback gradient compression:
    (params, opt_state, error_buf, batch) -> (params, opt_state, error_buf,
    metrics)."""
    loss_fn = build_loss_fn(cfg)
    grad_fn = jax.value_and_grad(loss_fn, has_aux=True)

    def train_step(params, opt_state, error_buf, batch):
        (_, metrics), grads = grad_fn(params, batch)
        grads, error_buf = comp.compress_grads_ef(grads, error_buf)
        params, opt_state, opt_metrics = optim.adamw_update(
            params, grads, opt_state, tc)
        return params, opt_state, error_buf, dict(metrics, **opt_metrics)

    return train_step


def build_prefill_step(cfg: ModelConfig, max_len: int) -> Callable:
    def prefill_step(params, batch):
        return model_lib.prefill(params, cfg, batch, max_len=max_len)
    return prefill_step


def build_decode_step(cfg: ModelConfig) -> Callable:
    def decode_step(params, cache, token):
        return model_lib.decode_step(params, cfg, cache, token)
    return decode_step


def build_paged_decode_step(cfg: ModelConfig,
                            use_pallas: Optional[bool] = None) -> Callable:
    """One-token decode over the page-pool cache; per-row positions.
    ``use_pallas`` None (served) runs the paged kernel wherever it
    compiles; False forces the XLA gather reference.

    (params, cache, token [B,1], active [B] bool) -> (logits, cache)."""
    def paged_decode_step(params, cache, token, active):
        return model_lib.decode_step_paged(params, cfg, cache, token,
                                           active, use_pallas=use_pallas)
    return paged_decode_step


def build_prefill_chunk_step(cfg: ModelConfig) -> Callable:
    """One prompt chunk per row into the page-pool cache.

    (params, cache, tokens [B,C], start [B], chunk_lens [B],
    active [B] bool) -> (last-valid-token logits [B,1,V], cache)."""
    def prefill_chunk_step(params, cache, tokens, start, chunk_lens,
                           active):
        return model_lib.prefill_chunk(params, cfg, cache, tokens,
                                       start, chunk_lens, active)
    return prefill_chunk_step


def build_serve_step(cfg: ModelConfig) -> Callable:
    """The dry-run's decode entry: one new token, greedy sample.

    (params, {"token", "cache"}) -> (next_token [B,1], cache)."""
    def serve_step(params, token, cache):
        logits, cache = model_lib.decode_step(params, cfg, cache, token)
        nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return nxt, cache
    return serve_step
