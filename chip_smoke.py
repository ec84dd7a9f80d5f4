#!/usr/bin/env python3
"""Bring-up check: serve qwen3-0.6b at its published widths on a TPU.

    python chip_smoke.py             # one chip
    python chip_smoke.py --chips 4   # four replicas behind the router

With no option it drives the normal serving entry point
(``repro.launch.serve``'s ``main``, in this process) over 8 requests at
full width with random weights drawn from ``--seed``, checks that every
request got all its tokens, that the paged decode step holds the Pallas
kernel compiled (``tpu_custom_call``), and that one decode step's logits
from the kernel agree with the XLA gather reference on the same cache.

``--chips 4`` runs only the replica check: four replicas with the
``least-loaded`` router, once with one device each and once all on one
device; every backend must sit on its own device in the first run, and
the token streams of the two runs must be identical.

Everything runs in one process, since a chip belongs to one process.
With no TPU the script exits non-zero before printing any result.  The
last line of standard output is a JSON object naming the device.  The
times printed are those of this check, not a benchmark.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "qwen3-0.6b"
#: kernel-vs-gather logits: the largest absolute difference may be this
#: share of the largest reference logit.  Both paths read the same bf16
#: cache; they differ in summation order and in the reference rounding
#: its softmax weights to bf16 before the PV product, which every bf16
#: layer carries forward.  With the kernel interpreted on a CPU, at full
#: width and 1 to 14 layers, the share measured 0.009 to 0.016; a wrong
#: page, head or mask moves logits by a good part of their spread.
LOGITS_RTOL = 5e-2
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


class CompileClock:
    """Sums JAX's XLA-compile durations (a persistent-cache hit is
    counted as its load time)."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event, duration, **_):
        if event == BACKEND_COMPILE:
            self.seconds += duration


def check(ok: bool, what: str) -> None:
    if not ok:
        raise SystemExit(f"chip_smoke FAILED: {what}")


def tpu_devices():
    import jax
    devices = jax.devices()
    first = devices[0]
    print(f"device: platform={first.platform} kind={first.device_kind} "
          f"count={len(devices)}", flush=True)
    if first.platform != "tpu":
        raise SystemExit(f"chip_smoke: no TPU found (JAX's first device is "
                         f"{first.platform!r}); this check runs only on a "
                         f"chip")
    return devices


def serve(argv, clock, devices=None):
    """One run of the serving entry point; returns (engine, summary,
    XLA compile seconds, wall seconds excluding them)."""
    from repro.launch import serve as serve_cli
    c0, t0 = clock.seconds, time.perf_counter()
    engine, summary = serve_cli.main(argv, devices=devices)
    wall = time.perf_counter() - t0
    compile_s = clock.seconds - c0
    n = len(engine.requests)
    check(summary["completed"] == n,
          f"{summary['completed']} of {n} requests completed")
    for r in engine.requests:
        check(len(r.tokens) == r.max_new_tokens,
              f"request {r.rid} got {len(r.tokens)} of "
              f"{r.max_new_tokens} tokens")
    return engine, summary, compile_s, wall - compile_s


def filled_cache(be, batch: int, rng):
    """A paged cache shaped like ``be``'s, whose rows hold random
    contexts written by the served prefill-chunk step."""
    import jax
    from repro.models import model as model_lib
    from repro.serve import pages_for
    from repro.train.step import build_prefill_chunk_step
    cfg, page = be.cfg, be.page_size
    max_pages = pages_for(be.max_len, page)
    num_pages = be.alloc.num_pages
    cache = model_lib.init_paged_cache(cfg, batch, num_pages, page,
                                       max_pages=max_pages)
    # each row owns max_pages distinct pages, scattered over the pool
    pages = rng.permutation(np.arange(1, num_pages))[:batch * max_pages]
    cache["table"] = jax.numpy.asarray(
        pages.reshape(batch, max_pages), jax.numpy.int32)
    lens = rng.integers(page + 1, max_pages * page - 1, batch)
    C = be.prefill_chunk
    tokens = rng.integers(3, cfg.vocab_size,
                          (batch, -(-int(lens.max()) // C) * C))
    chunk = jax.jit(build_prefill_chunk_step(cfg), donate_argnums=(1,))
    for start in range(0, int(lens.max()), C):
        cl = np.clip(lens - start, 0, C).astype(np.int32)
        _, cache = chunk(be.params, cache,
                         tokens[:, start:start + C].astype(np.int32),
                         np.full(batch, start, np.int32), cl, cl > 0)
    check(np.array_equal(np.asarray(cache["lens"]), lens),
          "prefill chunks left the wrong lengths")
    return cache


def kernel_vs_gather(be, batch: int, seed: int):
    """Compile the served decode step (kernel) and the XLA gather
    reference for one cache; return (max |diff|, max |reference|)."""
    import jax
    from repro.train.step import build_paged_decode_step
    rng = np.random.default_rng(seed)
    cache = filled_cache(be, batch, rng)
    token = rng.integers(3, be.cfg.vocab_size, (batch, 1)).astype(np.int32)
    active = np.ones((batch,), bool)
    args = (be.params, cache, token, active)
    kernel = jax.jit(build_paged_decode_step(be.cfg)).lower(*args).compile()
    check("tpu_custom_call" in kernel.as_text(),
          "the compiled paged decode step holds no tpu_custom_call")
    print("paged decode step: tpu_custom_call present (kernel compiled)")
    gather = jax.jit(build_paged_decode_step(be.cfg, use_pallas=False)) \
        .lower(*args).compile()
    check("tpu_custom_call" not in gather.as_text(),
          "the gather reference holds a kernel")
    lk = np.asarray(kernel(*args)[0], np.float32)
    lg = np.asarray(gather(*args)[0], np.float32)
    check(lk.shape == (batch, 1, be.cfg.vocab_size),
          f"logits shape {lk.shape}")
    check(bool(np.isfinite(lk).all() and np.isfinite(lg).all()),
          "non-finite logits")
    return float(np.abs(lk - lg).max()), float(np.abs(lg).max())


def one_chip(seed: int, clock: CompileClock) -> None:
    from repro import kernels
    check(not kernels.interpret_default(),
          "kernels would run in the Pallas interpreter on this device")
    print("kernels: compiled")
    argv = ["--arch", ARCH, "--requests", "8", "--prompt-len", "512",
            "--decode-steps", "32", "--max-batch", "8", "--page-size", "16",
            "--budget-gb", "4", "--seed", str(seed)]
    engine, summary, compile_s, serve_s = serve(argv, clock)
    tokens = sum(len(r.tokens) for r in engine.requests)
    print(f"bring-up serve: {summary['completed']} requests at full "
          f"width, {tokens} tokens, every request complete; XLA compile "
          f"(or compile-cache load) {compile_s:.2f} s, serving wall time "
          f"excluding it {serve_s:.2f} s")
    c0 = clock.seconds
    diff, scale = kernel_vs_gather(engine.backends[0], 8, seed)
    tol = LOGITS_RTOL * scale
    print(f"kernel vs gather, one full-width decode step: max |logit "
          f"diff| {diff:.5f} (tolerance {tol:.5f} = {LOGITS_RTOL} x max "
          f"|logit| {scale:.3f}); compile {clock.seconds - c0:.2f} s")
    check(diff <= tol, f"logits differ by {diff} > {tol}")


def four_replicas(devices, seed: int, clock: CompileClock) -> None:
    import jax
    check(len(devices) >= 4, f"--chips 4 needs 4 devices, JAX sees "
                             f"{len(devices)}")
    argv = ["--arch", ARCH, "--requests", "8", "--prompt-len", "128",
            "--decode-steps", "16", "--max-batch", "4", "--page-size",
            "16", "--budget-gb", "8", "--replicas", "4", "--router",
            "least-loaded", "--seed", str(seed)]
    runs = {}
    for name, devs in (("one device per replica", devices[:4]),
                       ("all replicas on one device", devices[:1])):
        engine, summary, compile_s, serve_s = serve(argv, clock, devs)
        placed = []
        for be in engine.backends:
            held = {d for x in jax.tree.leaves((be.params, be._cache))
                    for d in x.devices()}
            check(held == {be.device},
                  f"a backend pinned to {be.device} holds arrays on {held}")
            placed.append(be.device)
        streams = {r.rid: list(r.tokens) for r in engine.requests}
        runs[name] = (placed, streams, summary["node_steps"])
        print(f"{name}: devices {[d.id for d in placed]}, node steps "
              f"{summary['node_steps']}; XLA compile {compile_s:.2f} s, "
              f"serving wall time excluding it {serve_s:.2f} s")
    (spread, s1, n1), (together, s2, n2) = runs.values()
    check(len(set(spread)) == 4, f"replicas share devices: {spread}")
    check(len(set(together)) == 1, f"replicas spread: {together}")
    check(n1 == n2, f"routing differs: {n1} vs {n2}")
    check(s1 == s2, "token streams differ between the two placements")
    print("four replicas on four devices: token streams identical to the "
          "one-device run")


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="4 runs only the replica-placement check")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    from repro.utils.compile_cache import enable_compile_cache
    cache_dir = enable_compile_cache()
    devices = tpu_devices()
    print(f"compile cache: {cache_dir}")
    clock = CompileClock()
    if args.chips == 4:
        four_replicas(devices, args.seed, clock)
    else:
        one_chip(args.seed, clock)
    first = devices[0]
    print(json.dumps({"ok": True, "device": {
        "platform": first.platform, "kind": first.device_kind,
        "count": len(devices)}}))


if __name__ == "__main__":
    main()
