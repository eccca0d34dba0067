"""Chip smoke test: the LLM-42 serving path on one TPU, at full width.

Drives phi3-mini-3.8b (32 layers, d_model 3072, 32 heads of 96, vocab
32064, bf16 weights drawn from a seed) through the normal entry point,
``repro.launch.serve.main`` -> ``Engine`` -> scheduler -> block pool ->
verifier, in LLM42 mode with paged KV and the prefix cache on, then checks
on the chip:

1. the device is a TPU (anything else exits non-zero before any work);
2. 8 requests (4 deterministic, 128-token prompts, 32 new tokens) are
   served cold, then 8 more on the warm engine, timed around work that
   ends in ``block_until_ready``;
3. the deterministic requests, served again alone on a new engine with a
   different batch size, commit bitwise the same token streams;
4. the compiled paged-attention kernels (commit, and the fast path at 4
   splits) agree with their plain reference ``kernels.ref.paged_attention``
   at the same shapes, and the engine's decode step lowers to a program
   holding the kernel.

The last line of stdout is ``{"ok": true, "device": {...}}``; any failed
check exits non-zero before it.  One process, no subprocesses.

    python chip_smoke.py      # from the root of a checkout, on a TPU host
"""

from __future__ import annotations

import json
import sys
import time
from pathlib import Path

SRC = Path(__file__).resolve().parent / "src"
ARCH = "phi3-mini-3.8b"
N_REQ, N_DET, PROMPT, NEW = 8, 4, 128, 32
SERVE_ARGV = [
    "--arch", ARCH, "--requests", str(N_REQ), "--det-ratio", "0.5",
    "--prompt-len", str(PROMPT), "--max-new", str(NEW),
    "--max-batch", str(N_REQ), "--num-blocks", "128",
    "--mode", "llm42", "--prefix-cache", "on",
    # seed 8 draws exactly N_DET deterministic requests of N_REQ
    "--seed", "8",
]
#: compiled kernel vs XLA reference, both f32 at HIGHEST precision on the
#: MXU: they may order the 96-wide dot and the S-wide softmax sums
#: differently, which moves results by a few f32 ulps (~1e-6 relative);
#: 1e-4 keeps that margin and still catches a bf16-precision pass (~1e-2).
KERNEL_TOL = 1e-4


def fail(msg: str) -> None:
    print(f"FAIL: {msg}", flush=True)
    sys.exit(1)


def peak_gb(dev) -> str:
    stats = dev.memory_stats() or {}
    return (f"peak_bytes_in_use {stats.get('peak_bytes_in_use', 0) / 1e9:.3f} GB"
            f" (bytes_in_use {stats.get('bytes_in_use', 0) / 1e9:.3f} GB,"
            f" limit {stats.get('bytes_limit', 0) / 1e9:.3f} GB)")


def det_streams(done) -> dict:
    return {r.rid: list(r.committed) for r in done if r.sampling.is_deterministic}


def serve_timed(engine, reqs, stats):
    """Submit ``reqs`` at once and drain; (finished, wall s, compile line)."""
    import jax

    for r in reqs:
        engine.submit(r)
    snap = stats.snapshot()
    t0 = time.perf_counter()
    done = engine.run()
    jax.block_until_ready(engine.pool.data)
    return done, time.perf_counter() - t0, stats.since(snap)


def check_kernel(dev) -> None:
    """Compiled paged kernels vs their reference, phi3 shapes."""
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import paged_attention as pk
    from repro.kernels import ref

    B, H, D, DP, bs, nblk, NB = 8, 32, 96, 128, 16, 32, 128
    rng = np.random.default_rng(0)
    null_bid = NB
    k = rng.standard_normal((NB + 2, H, bs, DP)).astype(np.float32)
    v = rng.standard_normal((NB + 2, H, bs, DP)).astype(np.float32)
    k[null_bid] = v[null_bid] = 0.0
    pos = np.full((NB + 2, bs), -1, np.int32)
    tables = np.full((B, nblk), -1, np.int32)
    q_pos = np.zeros((B,), np.int32)
    free = list(rng.permutation(NB))
    for b in range(B):
        length = int(rng.integers(1, 16 * bs))  # rows of 1..255 tokens
        for j in range(-(-length // bs)):
            bid = free.pop()
            tables[b, j] = bid
            fill = min(bs, length - j * bs)
            pos[bid, :fill] = np.arange(j * bs, j * bs + fill)
        q_pos[b] = length - 1
    args = (
        jnp.asarray(rng.standard_normal((B, H, D)), jnp.bfloat16),
        jnp.asarray(k, jnp.bfloat16), jnp.asarray(v, jnp.bfloat16),
        jnp.asarray(pos), jnp.asarray(tables), jnp.asarray(q_pos),
    )
    ref_fn = jax.jit(
        ref.paged_attention, static_argnames=("null_bid", "kv_splits")
    )
    # the commit kernel, and the fast-path kernel at the split count the
    # decode batch of 8 runs (f32 combine, so the same tolerance applies)
    for name, got, want in (
        ("commit", pk.paged_attention(*args, null_bid=null_bid, interpret=False),
         ref_fn(*args, null_bid=null_bid)),
        ("fastpath kv_splits=4",
         pk.paged_attention_fast(*args, kv_splits=4, null_bid=null_bid,
                                 interpret=False),
         ref_fn(*args, null_bid=null_bid, kv_splits=4)),
    ):
        err = float(jnp.max(jnp.abs(got - want)))
        scale = float(jnp.max(jnp.abs(want)))
        finite = bool(jnp.all(jnp.isfinite(got)))
        print(f"kernel check: paged_attention {name} (compiled) vs "
              f"ref.paged_attention at B={B} H=KV={H} D={D} (stored {DP}) "
              f"bs={bs} nblk={nblk}: max |diff| {err:.3e} (max |ref| "
              f"{scale:.3f}), tolerance {KERNEL_TOL:.0e}, finite={finite}")
        if not finite or err > KERNEL_TOL * max(1.0, scale):
            fail(f"compiled paged kernel ({name}) disagrees with its reference")


def check_decode_lowering(engine) -> None:
    """The engine's W=1 decode step, lowered for this TPU, must hold the
    Pallas kernel (``tpu_custom_call``), not the gathered-view fallback."""
    import jax
    import jax.numpy as jnp

    B = N_REQ
    nblk = engine.pool.blocks_per_table
    i32 = jax.ShapeDtypeStruct((B,), jnp.int32)
    dargs = (
        i32, jax.ShapeDtypeStruct((B, nblk), jnp.int32), i32, i32, i32,
        jax.ShapeDtypeStruct((B,), jnp.float32), i32, i32,
    )
    schedule = engine._decode_schedule(B)
    text = engine._decode_fn(B, schedule).lower(
        engine.params, engine.pool.data, *dargs
    ).as_text()
    has = "tpu_custom_call" in text
    print(f"decode step (B={B}, {schedule}) lowers with tpu_custom_call: {has}")
    if not has:
        fail("the decode step does not reach the paged-attention kernel")


def main() -> None:
    if not (SRC / "repro").is_dir():
        fail(f"no src/repro next to {Path(__file__).name}: run it from a "
             "checkout of the repository")
    sys.path.insert(0, str(SRC))
    from repro.launch import compile_cache

    cache_dir = compile_cache.enable()
    stats = compile_cache.stats()
    import jax

    from repro.launch import serve

    dev = jax.devices()[0]
    print(serve.device_line(), flush=True)
    if dev.platform != "tpu":
        fail(f"no TPU: JAX found platform {dev.platform!r}")
    print(f"compile cache: {cache_dir}")

    # 1. cold serve through the CLI entry point (builds the weights)
    t0 = time.perf_counter()
    engine, done = serve.main(SERVE_ARGV)
    print(f"phase serve (cold): {time.perf_counter() - t0:.3f} s including "
          f"weight init; {len(engine._fns) + 1} step programs built; "
          f"{peak_gb(dev)}", flush=True)
    first = det_streams(done)
    if len(done) != N_REQ or len(first) != N_DET:
        fail(f"served {len(done)} requests with {len(first)} deterministic, "
             f"expected {N_REQ} and {N_DET}")
    if any(len(s) != NEW for s in first.values()):
        fail("a deterministic request committed the wrong number of tokens")

    # 2. warm: 8 new prompts of the same lengths on the same engine
    args = serve.build_parser().parse_args(SERVE_ARGV)
    cfg = engine.cfg
    warm = serve.build_requests(cfg, N_REQ, 0.0, NEW, seed=99, in_len=PROMPT)
    for r, old in zip(warm, sorted(done, key=lambda r: r.rid)):
        r.rid += 100  # distinct from the cold batch on the same engine
        r.sampling.is_deterministic = old.sampling.is_deterministic
    wdone, wall, comp = serve_timed(engine, warm, stats)
    wdone = [r for r in wdone if r.rid >= 100]
    toks = sum(r.num_output for r in wdone)
    rb = sum(r.num_rollbacks for r in wdone)
    rec = sum(r.num_recomputed_tokens for r in wdone)
    print(f"phase serve (warm): {len(wdone)} requests, {toks} tokens in "
          f"{wall:.3f} s wall on {dev.device_kind} ({toks / wall:.1f} tok/s, "
          f"compile excluded only if none happened: {comp}); "
          f"rollbacks={rb} recomputed_tokens={rec}; {peak_gb(dev)}", flush=True)

    # 3. determinism: the deterministic requests again, alone, batch of 4
    check_decode_lowering(engine)
    params = engine.params
    del engine, done, wdone
    alone = [r for r in serve.build_requests(
        cfg, N_REQ, args.det_ratio, NEW, args.seed, in_len=PROMPT)
        if r.sampling.is_deterministic]
    engine2 = serve.make_engine(args, cfg, params, cfg, max_batch=N_DET)
    ddone, wall2, comp2 = serve_timed(engine2, alone, stats)
    second = det_streams(ddone)
    same = sum(first[rid] == second.get(rid) for rid in first)
    print(f"phase determinism: {len(alone)} deterministic requests alone at "
          f"max_batch={N_DET} (first run: {N_REQ} mixed at max_batch={N_REQ}): "
          f"{same}/{len(first)} committed streams bitwise identical; "
          f"{wall2:.3f} s wall, {comp2}; {peak_gb(dev)}", flush=True)
    if same != len(first):
        fail("deterministic streams changed with co-traffic")
    del engine2

    # 4. the compiled kernel against its reference
    check_kernel(dev)
    print(f"done: {peak_gb(dev)}")
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices()),
    }}), flush=True)


if __name__ == "__main__":
    main()
