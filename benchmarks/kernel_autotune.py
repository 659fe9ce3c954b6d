"""Block-size / layout autotune sweep for the fused SPS attention kernel.

Sweeps the fused ``repro.kernels.sps_attn`` Pallas kernel over a
(bq, bk) tile grid crossed with the two context layouts:

  vpu : V^T packed along the sequence dim, context via AND+popcount —
        the fully binary datapath (decode/deploy configuration).
  mxu : V as ±1 bf16 values, context via dot-general on the MXU — the
        compute-bound prefill configuration.

Every configuration is gated for exactness before it is timed: the
kernel output must match the dense unpacked oracle
(``repro.kernels.sps_attn.ref.sps_attention``) bit for bit — a config
that loses the Eq. 7 pad correction or mis-tiles the causal mask is
reported as ``exact: false`` and excluded from the winner, never
silently ranked.  Timings are medians of repeated steps after a
compile/warmup pass.

Off-TPU the kernel runs in interpret mode, so step-ms numbers there are
a smoke/correctness face (the CI tiny sweep), not a perf face; on real
TPU backends the same sweep is the tuning tool.  ``REPRO_FORCE_INTERPRET``
(see ``repro.kernels.interpret_mode``) forces either mode.

Run:  PYTHONPATH=src python benchmarks/kernel_autotune.py --tiny --json out.json
Also importable: ``autotune_sps(...)`` returns the result dict, and
``serve_throughput.py --autotune`` embeds it in its JSON report.
"""
from __future__ import annotations

import argparse
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import packing
from repro.kernels import interpret_mode
from repro.kernels.sps_attn import kernel as sps_kernel
from repro.kernels.sps_attn import ref as sps_ref
from repro.launch.runtime import use_compile_cache

PATHS = ("vpu", "mxu")
DEFAULT_BLOCKS = (128, 256, 512)
TINY_BLOCKS = (32, 64)


def _median_step_ms(fn, *args, iters: int = 5) -> float:
    """Median wall-clock of ``fn(*args)`` after a warmup/compile call."""
    fn(*args).block_until_ready()
    times = []
    for _ in range(iters):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def make_operands(rng, h: int, l: int, d_h: int):
    """Random packed Q/K head bits (+pad-0 last word), ±1 V in both
    layouts, and per-head integer thresholds."""
    q = rng.integers(0, 2, (h, l, d_h)).astype(np.uint32)
    k = rng.integers(0, 2, (h, l, d_h)).astype(np.uint32)
    v = (2 * rng.integers(0, 2, (h, l, d_h)) - 1).astype(np.int32)
    q_bits = packing.pack_bits(jnp.asarray(q))
    k_bits = packing.pack_bits(jnp.asarray(k))
    v_vals = jnp.asarray(v)
    vt_bits = sps_ref.v_transpose_packed(v_vals)
    theta = jnp.asarray(rng.integers(-d_h // 4, d_h // 4, (h,)), jnp.int32)
    return q_bits, k_bits, v_vals, vt_bits, theta


def autotune_sps(*, h: int = 4, l: int = 512, d_h: int = 64,
                 blocks=DEFAULT_BLOCKS, paths=PATHS, iters: int = 5,
                 seed: int = 0, causal: bool = True) -> dict:
    """Sweep (path, bq, bk) over the fused SPS kernel; return a dict with
    the full ``sweep`` list ({path, bq, bk, step_ms, exact}), the
    exact-and-fastest ``best`` entry, and the problem shape."""
    rng = np.random.default_rng(seed)
    q_bits, k_bits, v_vals, vt_bits, theta = make_operands(rng, h, l, d_h)
    oracle = sps_ref.sps_attention(q_bits, k_bits, v_vals, theta,
                                   d_h=d_h, causal=causal)
    interp = interpret_mode()
    sweep = []
    for path in paths:
        v_in = vt_bits if path == "vpu" else v_vals.astype(jnp.bfloat16)
        for bq in blocks:
            for bk in blocks:
                out = sps_kernel.sps_attention(
                    q_bits, k_bits, v_in, theta, d_h=d_h, causal=causal,
                    path=path, bq=bq, bk=bk, interpret=interp)
                exact = bool((out == oracle).all())
                step_ms = _median_step_ms(
                    lambda: sps_kernel.sps_attention(
                        q_bits, k_bits, v_in, theta, d_h=d_h,
                        causal=causal, path=path, bq=bq, bk=bk,
                        interpret=interp),
                    iters=iters)
                sweep.append({"path": path, "bq": bq, "bk": bk,
                              "step_ms": step_ms, "exact": exact})
    exact_entries = [e for e in sweep if e["exact"]]
    best = min(exact_entries, key=lambda e: e["step_ms"]) \
        if exact_entries else None
    return {"shape": {"h": h, "l": l, "d_h": d_h, "causal": causal},
            "backend": jax.default_backend(),
            "interpret": interp,
            "sweep": sweep, "best": best}


def main(argv=None):
    p = argparse.ArgumentParser()
    p.add_argument("--heads", type=int, default=4)
    p.add_argument("--seq-len", type=int, default=512)
    p.add_argument("--head-dim", type=int, default=64)
    p.add_argument("--blocks", type=int, nargs="+", default=None,
                   help="bq/bk candidates (cartesian product)")
    p.add_argument("--iters", type=int, default=5)
    p.add_argument("--tiny", action="store_true",
                   help="CI smoke: tiny shape + tiny block grid")
    p.add_argument("--json", default=None,
                   help="write the sweep result dict as JSON (the CI "
                        "bench-smoke job uploads this artifact and fails "
                        "on a missing or empty sweep)")
    p.add_argument("--seed", type=int, default=0)
    args = p.parse_args(argv)

    use_compile_cache()
    if args.tiny:
        h, l, iters = 2, 96, 2
        blocks = tuple(args.blocks) if args.blocks else TINY_BLOCKS
    else:
        h, l, iters = args.heads, args.seq_len, args.iters
        blocks = tuple(args.blocks) if args.blocks else DEFAULT_BLOCKS

    result = autotune_sps(h=h, l=l, d_h=args.head_dim, blocks=blocks,
                          iters=iters, seed=args.seed)
    face = ("interpret-mode — correctness/smoke face, not perf"
            if result["interpret"] else "compiled")
    print(f"[sps_attn autotune] H={h} L={l} d_h={args.head_dim} "
          f"backend={result['backend']} ({face})")
    for e in sorted(result["sweep"], key=lambda e: e["step_ms"]):
        flag = "" if e["exact"] else "  MISMATCH vs oracle"
        print(f"  {e['path']:3s} bq={e['bq']:4d} bk={e['bk']:4d}  "
              f"{e['step_ms']:8.2f} ms{flag}")
    if result["best"] is None:
        raise SystemExit("autotune: no configuration matched the oracle")
    b = result["best"]
    print(f"  best: {b['path']} bq={b['bq']} bk={b['bk']} "
          f"({b['step_ms']:.2f} ms)")
    if args.json:
        with open(args.json, "w") as f:
            json.dump(result, f, indent=2)
        print(f"  wrote {args.json}")
    return result


if __name__ == "__main__":
    main()
