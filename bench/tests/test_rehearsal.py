"""Compile rehearsals for a described v5e: each configuration's served
programs at its deployment settings, and the reference at its longest
check request, must fit one chip's HBM.  Nothing runs; these catch a
pool, chunk or ``max_len`` that would run out of memory at the batch
bucket every window runs at (the warm-up fixes the backend there).

The topology is described inside a module fixture, never at import, and
the persistent compile cache is off meanwhile (a compile for a described
chip cannot be read back without one)."""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from harness import spec, traffic, weights

#: what XLA may use of one v5e's HBM (its compile errors report 15.75G)
#: less 0.75 GB for the runtime's reservation and the eager token read
USABLE = 15.75e9 - 0.75e9
CONFIGS = sorted(c["name"] for c in json.loads(
    (spec.ROOT / "BENCHMARK.json").read_text())["configs"])


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


def _config(name):
    bm = json.loads((spec.ROOT / "BENCHMARK.json").read_text())
    entry = next(c for c in bm["configs"] if c["name"] == name)
    return json.loads((spec.ROOT / entry["file"]).read_text())


def _on(chip, tree):
    return jax.tree.map(lambda x: jax.ShapeDtypeStruct(
        x.shape, x.dtype, sharding=chip), tree)


def _bytes(compiled):
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.output_size_in_bytes
            + m.temp_size_in_bytes - m.alias_size_in_bytes)


@pytest.mark.parametrize("name", CONFIGS)
def test_served_programs_fit(one_chip, compiled_kernels, name):
    from repro.models import model as model_lib
    from repro.train.step import (build_paged_decode_step,
                                  build_prefill_chunk_step)
    config = _config(name)
    dep = config["deployment"]
    cfg = spec.program_config(config)
    cap = 1 << max(int(dep["max_batch"]) - 1, 0).bit_length()
    maxp = -(-int(dep["max_len"]) // int(dep["page_size"]))
    params = _on(one_chip, model_lib.abstract(cfg))
    cache = _on(one_chip, model_lib.init_paged_cache(
        cfg, cap, int(dep["num_pages"]), int(dep["page_size"]),
        abstract_only=True, max_pages=maxp))
    row = jax.ShapeDtypeStruct((cap,), jnp.int32, sharding=one_chip)
    act = jax.ShapeDtypeStruct((cap,), jnp.bool_, sharding=one_chip)
    chunk = jax.jit(build_prefill_chunk_step(cfg), donate_argnums=(1,)) \
        .lower(params, cache, jax.ShapeDtypeStruct(
            (cap, int(dep["prefill_chunk"])), jnp.int32, sharding=one_chip),
            row, row, act).compile()
    decode = jax.jit(build_paged_decode_step(cfg), donate_argnums=(1,)) \
        .lower(params, cache, jax.ShapeDtypeStruct(
            (cap, 1), jnp.int32, sharding=one_chip), act).compile()
    assert "tpu_custom_call" in decode.as_text()
    for compiled in (chunk, decode):
        assert _bytes(compiled) <= USABLE, (name, _bytes(compiled) / 1e9)


@pytest.mark.parametrize("name", CONFIGS)
def test_reference_fits_beside_the_weights(one_chip, name):
    """One reference layer at the longest check request, with every
    weight of the configuration resident (the KV pool is freed first)."""
    from reference import qwen3
    config = _config(name)
    mixes = {w["traffic"] for w in json.loads(
        (spec.ROOT / "BENCHMARK.json").read_text())["workloads"]
        if w["config"] == name}
    longest = max(p + n - 1 for mix in mixes
                  for p, n in traffic.round_pairs(json.loads(
                      (spec.BENCH / "traffic" / f"{mix}.json").read_text())))
    T = -(-longest // qwen3.Q_BLOCK) * qwen3.Q_BLOCK
    params = _on(one_chip, jax.eval_shape(
        lambda: weights.make(config, 0)))
    resident = sum(x.size * x.dtype.itemsize
                   for x in jax.tree.leaves(params))
    shape = (float(config["rms_norm_eps"]), float(config["rope_theta"]),
             int(config["num_attention_heads"]),
             int(config["num_key_value_heads"]), int(config["head_dim"]))
    x = jax.ShapeDtypeStruct((T, int(config["hidden_size"])), jnp.float32,
                             sharding=one_chip)
    i = jax.ShapeDtypeStruct((), jnp.int32, sharding=one_chip)
    for fp8 in (False, True):
        layer = qwen3._layer.lower(shape, x, params["blocks"], i,
                                   fp8).compile()
        m = layer.memory_analysis()
        assert resident + m.temp_size_in_bytes + m.output_size_in_bytes \
            + x.size * 4 <= USABLE
