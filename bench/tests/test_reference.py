"""The float32 reference against the served path's own prefill-chunk
and paged-decode logits, on the CPU at a tiny size.

The program runs in bf16 and the reference in float32 at the highest
matmul precision, so logits differ by bf16 rounding: at this size a few
thousandths of their spread.  A wrong norm, rotation, head grouping,
mask or cache entry moves them by a good part of it.
"""
import jax
import jax.numpy as jnp
import numpy as np

from harness import spec, weights
from reference import qwen3

#: largest |program - reference| logit, as a share of the largest
#: reference logit (bf16 rounding through three tiny layers)
RTOL = 0.03


def _served_logits(cell, params, prompt, steps):
    """Prefill ``prompt`` in the cell's chunks through the program's
    paged prefill-chunk step, then decode ``steps`` greedy tokens through
    its paged decode step; returns (logits per position [steps+1, V],
    the tokens fed back)."""
    from repro.models import model as model_lib
    from repro.train.step import (build_paged_decode_step,
                                  build_prefill_chunk_step)
    cfg = spec.program_config(cell.config)
    dep = cell.deployment
    page, C = dep["page_size"], dep["prefill_chunk"]
    maxp = -(-dep["max_len"] // page)
    cache = model_lib.init_paged_cache(cfg, 1, dep["num_pages"], page,
                                       max_pages=maxp)
    cache["table"] = jnp.arange(1, maxp + 1, dtype=jnp.int32)[None]
    chunk = jax.jit(build_prefill_chunk_step(cfg))
    decode = jax.jit(build_paged_decode_step(cfg))
    one = np.ones((1,), bool)
    for s in range(0, len(prompt), C):
        part = prompt[s:s + C]
        toks = np.full((1, C), 3, np.int32)
        toks[0, :len(part)] = part
        logits, cache = chunk(params, cache, toks, np.array([s], np.int32),
                              np.array([len(part)], np.int32), one)
    out, fed = [np.asarray(logits[0, 0], np.float32)], []
    for _ in range(steps):
        tok = int(np.argmax(out[-1]))
        fed.append(tok)
        logits, cache = decode(params, cache, np.array([[tok]], np.int32),
                               one)
        out.append(np.asarray(logits[0, 0], np.float32))
    return np.stack(out), fed


def test_reference_matches_served_prefill_and_decode(tiny_cell):
    params = weights.make(tiny_cell.config, 5)
    rng = np.random.default_rng(5)
    prompt = rng.integers(10, tiny_cell.config["vocab_size"], 21).tolist()
    served, fed = _served_logits(tiny_cell, params, prompt, 6)
    rows = np.arange(len(prompt) - 1, len(prompt) + len(fed))
    ref = np.asarray(qwen3.logits(params, tiny_cell.config, prompt + fed,
                                  rows))
    err = np.abs(served - ref).max()
    assert err <= RTOL * np.abs(ref).max(), (err, np.abs(ref).max())
    assert (served.argmax(-1) == ref.argmax(-1)).all()


def test_control_is_further_from_the_reference_than_the_program(tiny_cell):
    """fp8 matmuls (the control) depart from float32 by more than the
    program's bf16 does."""
    params = weights.make(tiny_cell.config, 6)
    rng = np.random.default_rng(6)
    prompt = rng.integers(10, tiny_cell.config["vocab_size"], 21).tolist()
    served, fed = _served_logits(tiny_cell, params, prompt, 6)
    seq = prompt + fed
    rows = np.arange(len(prompt) - 1, len(seq))
    ref = np.asarray(qwen3.logits(params, tiny_cell.config, seq, rows))
    low = np.asarray(qwen3.logits(params, tiny_cell.config, seq, rows,
                                  fp8=True))
    assert np.abs(low - ref).max() > 3 * np.abs(served - ref).max()


def test_weights_match_the_program_layout(tiny_cell):
    from repro.models import model as model_lib
    cfg = spec.program_config(tiny_cell.config)
    weights.check_layout(weights.make(tiny_cell.config, 2**31 + 7),
                         model_lib.abstract(cfg))


def test_weights_follow_the_seed(tiny_cell):
    a = weights.make(tiny_cell.config, 11)
    b = weights.make(tiny_cell.config, 11)
    c = weights.make(tiny_cell.config, 12)
    same = jax.tree.map(lambda x, y: bool((x == y).all()), a, b)
    assert all(jax.tree.leaves(same))
    assert not bool((a["embed"] == c["embed"]).all())
