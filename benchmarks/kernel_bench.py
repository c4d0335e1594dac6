"""Kernel microbenchmarks: ``name,us_per_call,derived`` CSV.

On CPU the Pallas kernels are timed in their XLA-oracle form (interpret mode
measures Python emulation, not hardware); the kernel bodies themselves are
correctness-validated by tests/test_kernels.py.  `derived` reports the
achieved GFLOP/s of the oracle path as a lower-bound reference point.

``--quick`` shrinks the problem sizes for the CI smoke step; ``--json PATH``
writes the rows as JSON for the benchmark artifact trajectory.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp

from repro.compile_cache import enable_compile_cache
from repro.kernels.attention.ref import decode_ref, mha_ref
from repro.kernels.ssd.ref import ssd_ref


def _time(fn, *args, iters=5):
    out = fn(*args)
    jax.block_until_ready(out)
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6   # us


def bench_attention(quick: bool = False) -> list[tuple[str, float, str]]:
    key = jax.random.key(0)
    rows = []
    b, s, hq, hkv, d = 1, (512 if quick else 2048), 8, 2, 64
    q = jax.random.normal(key, (b, s, hq, d), jnp.bfloat16)
    k = jax.random.normal(key, (b, s, hkv, d), jnp.bfloat16)
    v = jax.random.normal(key, (b, s, hkv, d), jnp.bfloat16)
    f = jax.jit(lambda q_, k_, v_: mha_ref(q_, k_, v_, causal=True))
    us = _time(f, q, k, v)
    flops = 4 * b * s * s * hq * d
    rows.append((f"attn_prefill_{s}", us, f"{flops/us/1e3:.1f}GFLOPs"))

    kv_len = 1024 if quick else 4096
    q1 = jax.random.normal(key, (8, 1, hq, d), jnp.bfloat16)
    k1 = jax.random.normal(key, (8, kv_len, hkv, d), jnp.bfloat16)
    v1 = jax.random.normal(key, (8, kv_len, hkv, d), jnp.bfloat16)
    fd = jax.jit(lambda q_, k_, v_: decode_ref(q_, k_, v_,
                                               position=kv_len - 1))
    us = _time(fd, q1, k1, v1)
    bytes_ = 2 * 8 * kv_len * hkv * d * 2
    rows.append((f"attn_decode_{kv_len}", us, f"{bytes_/us/1e3:.1f}GB/s"))
    return rows


def bench_ssd(quick: bool = False) -> tuple[str, float, str]:
    key = jax.random.key(0)
    B, S, H, P, G, N, Q = 2, (256 if quick else 1024), 16, 64, 1, 64, 128
    x = jax.random.normal(key, (B, S, H, P), jnp.bfloat16)
    dt = jax.nn.softplus(jax.random.normal(key, (B, S, H)))
    a = -jnp.exp(jax.random.normal(key, (H,)) * 0.3)
    bb = jax.random.normal(key, (B, S, G, N), jnp.bfloat16)
    cc = jax.random.normal(key, (B, S, G, N), jnp.bfloat16)
    f = jax.jit(lambda *xs: ssd_ref(*xs, Q))
    us = _time(f, x, dt, a, bb, cc)
    flops = 2 * B * (S // Q) * H * Q * Q * (N + P)
    return (f"ssd_{S}", us, f"{flops/us/1e3:.1f}GFLOPs")


def run(quick: bool = False) -> list[tuple[str, float, str]]:
    rows = bench_attention(quick) + [bench_ssd(quick)]
    for name, us, derived in rows:
        print(f"{name},{us:.1f},{derived}")
    return rows


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--quick", action="store_true",
                    help="smaller problem sizes (CI smoke step)")
    ap.add_argument("--json", metavar="PATH",
                    help="write rows as JSON for the benchmark artifact")
    args = ap.parse_args()
    if args.json:     # fail fast on an unwritable path, not after the bench
        open(args.json, "a").close()
    rows = run(quick=args.quick)
    if args.json:
        with open(args.json, "w") as f:
            json.dump({"benchmark": "kernel_bench",
                       "device": str(jax.devices()[0]),
                       "quick": args.quick,
                       "rows": [{"name": n, "us_per_call": round(us, 2),
                                 "derived": d} for n, us, d in rows]},
                      f, indent=2)
        print(f"wrote {args.json}")


if __name__ == "__main__":
    main()
