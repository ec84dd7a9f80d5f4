"""Compile rehearsals for a described TPU v5e (no chip attached).

Each test compiles a kernel, or a full-width paged serve step, for one
chip of a described ``v5e:2x2`` topology: the TPU compiler refuses here
what it would refuse on the chip (misaligned blocks, scalar or vector
memory overflow, a program larger than HBM).  Nothing runs, so these
say nothing about results or times.

The topology is described inside a module fixture, never at import:
only one process may load the TPU library, and every test worker
imports this file.  Kernels are steered to compiled mode by
monkeypatching the one interpret decision, ``repro.kernels
.interpret_default`` — on this CPU host it would pick the interpreter,
whose output holds no ``tpu_custom_call``.
"""
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HBM_BYTES = 16 * 1024 ** 3          # one v5e chip
BF16 = jnp.bfloat16
I32 = jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means "cannot"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep the cache off meanwhile
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture
def compiled_kernels(monkeypatch):
    import repro.kernels
    monkeypatch.setattr(repro.kernels, "interpret_default", lambda: False)


def _compile(fn, *shapes):
    return jax.jit(fn).lower(*shapes).compile()


def _device_bytes(compiled) -> int:
    mem = compiled.memory_analysis()
    return (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)


def _assert_kernel_fits(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    assert _device_bytes(compiled) < HBM_BYTES


@pytest.mark.parametrize("batch,num_pages,max_pages", [
    (8, 281, 35),        # the bring-up serve: 8 x 512+32-token requests
    (64, 4400, 128),     # deployment pool: ~8 GB of KV over 28 layers
], ids=["smoke-pool", "deployment-pool"])
def test_paged_attention_compiles(one_chip, compiled_kernels, batch,
                                  num_pages, max_pages):
    """qwen3-0.6b widths (Hq 16, Hkv 8, D 128, page 16)."""
    from repro.kernels.paged_attention.ops import paged_attention

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((num_pages, 16, 8, 128), BF16)
    _assert_kernel_fits(_compile(
        paged_attention, S((batch, 1, 16, 128), BF16), pool, pool,
        S((batch, max_pages), I32), S((batch,), I32)))


def test_paged_attention_kernel_carries_its_name(one_chip,
                                                 compiled_kernels):
    """The compiled kernel's op is named ``paged_attention`` in its
    scope path, which is how a device trace finds it."""
    import re
    from repro.kernels.paged_attention.ops import paged_attention

    def S(shape, dt):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    pool = S((281, 16, 8, 128), BF16)
    text = _compile(paged_attention, S((8, 1, 16, 128), BF16), pool, pool,
                    S((8, 35), I32), S((8,), I32)).as_text()
    calls = [ln for ln in text.splitlines()
             if 'custom_call_target="tpu_custom_call"' in ln]
    assert len(calls) == 1
    assert re.search(r'op_name="[^"]*/paged_attention/pallas_call"',
                     calls[0])


def test_rmsnorm_compiles(one_chip, compiled_kernels):
    from repro.kernels.rmsnorm.ops import rmsnorm
    _assert_kernel_fits(_compile(
        rmsnorm,
        jax.ShapeDtypeStruct((8, 512, 1024), BF16, sharding=one_chip),
        jax.ShapeDtypeStruct((1024,), BF16, sharding=one_chip)))


def test_flash_attention_compiles(one_chip, compiled_kernels):
    from repro.kernels.flash_attention.ops import flash_attention
    q = jax.ShapeDtypeStruct((1, 2048, 16, 128), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((1, 2048, 8, 128), BF16, sharding=one_chip)
    _assert_kernel_fits(_compile(flash_attention, q, kv, kv))


def test_decode_attention_compiles(one_chip, compiled_kernels):
    from repro.kernels.decode_attention.ops import decode_attention
    q = jax.ShapeDtypeStruct((8, 1, 16, 128), BF16, sharding=one_chip)
    kv = jax.ShapeDtypeStruct((8, 4096, 8, 128), BF16, sharding=one_chip)
    ln = jax.ShapeDtypeStruct((8,), I32, sharding=one_chip)
    _assert_kernel_fits(_compile(decode_attention, q, kv, kv, ln))


def test_ssd_scan_compiles(one_chip, compiled_kernels):
    """mamba2-780m widths: 48 heads of P 64, state N 128, chunk 128."""
    from repro.kernels.ssd_scan.ops import ssd_scan

    def S(shape, dt=jnp.float32):
        return jax.ShapeDtypeStruct(shape, dt, sharding=one_chip)
    _assert_kernel_fits(_compile(
        lambda x, a, b, c: ssd_scan(x, a, b, c, chunk=128),
        S((1, 2048, 48, 64)), S((1, 2048, 48)), S((1, 2048, 1, 128)),
        S((1, 2048, 1, 128))))


def _full_width_step(one_chip, step, *inputs):
    """Compile a paged serve step of qwen3-0.6b at its published widths,
    shaped as the bring-up serve runs it (8 rows, 281 pages of 16
    tokens, 35-page tables); ``inputs`` are its per-call arrays."""
    from repro.configs import get_config
    from repro.models import model as model_lib
    cfg = get_config("qwen3-0.6b")

    def on_chip(tree):
        return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
            x.shape, x.dtype, sharding=one_chip), tree)
    params = on_chip(jax.eval_shape(
        lambda: model_lib.init(cfg, jax.random.key(0))))
    cache = on_chip(model_lib.init_paged_cache(
        cfg, 8, 281, 16, abstract_only=True, max_pages=35))
    compiled = jax.jit(step(cfg), donate_argnums=(1,)).lower(
        params, cache, *on_chip(inputs)).compile()
    # weights alone are 1.19 GB of bf16
    assert 1.0e9 < _device_bytes(compiled) < HBM_BYTES
    return compiled


def _kernel_calls(compiled) -> int:
    return sum('custom_call_target="tpu_custom_call"' in ln
               for ln in compiled.as_text().splitlines())


def test_full_width_paged_decode_step_compiles(one_chip, compiled_kernels):
    """The served decode step holds the paged kernel, compiled, and no
    other: a device trace finds the decode kernel as the decode
    program's one ``tpu_custom_call``."""
    from repro.train.step import build_paged_decode_step
    compiled = _full_width_step(
        one_chip, build_paged_decode_step,
        jax.ShapeDtypeStruct((8, 1), I32),
        jax.ShapeDtypeStruct((8,), jnp.bool_))
    assert _kernel_calls(compiled) == 1


def test_full_width_prefill_chunk_step_fits(one_chip, compiled_kernels):
    """The served chunk step holds the paged prefill kernel, compiled,
    and no array as wide as a row's whole table (35 pages of 16 tokens:
    the gather path's dense K/V copy and its logits are 560 wide)."""
    import re
    from repro.train.step import build_prefill_chunk_step
    row = jax.ShapeDtypeStruct((8,), I32)
    compiled = _full_width_step(
        one_chip, build_prefill_chunk_step,
        jax.ShapeDtypeStruct((8, 32), I32), row, row,
        jax.ShapeDtypeStruct((8,), jnp.bool_))
    assert _kernel_calls(compiled) == 1
    assert not re.search(r"\[[0-9,]*\b560\b", compiled.as_text())
