#!/usr/bin/env python3
"""Measure the chip's int32 vector-unit element-operation peak.

The banded DP runs on the vector unit (int32 add / subtract / max /
compare / select), not the matrix unit, and no vendor table states that
peak. This probe runs one Pallas kernel whose body is `chains`
independent (8, 128) int32 tiles held in VMEM, each updated `iters`
times by an add and a max, so the compiler can issue the chains side by
side. Each update is one int32 operation on each of 1,024 elements:

    ops = iters x chains x 2 x 1024

It times `reps` calls with the host clock around `block_until_ready`
(the iterations are set so that each call does `OPS` operations, tens of
milliseconds at the peak, so the clock's half millisecond does not
matter), doubling the chain count until two doublings in a row gain
less than `PLATEAU`, and prints every chain count's rate and the best as
JSON. Run it once per device kind on the chip and copy the reading into
`bench/peaks.json`; the benchmark does not re-measure it per run:

    python bench/peaks_probe.py
"""

from __future__ import annotations

import functools
import json
import sys
import time

#: Updates per loop trip, written out so the loop's own cost is small.
UNROLL = 8
#: Element operations per timed call.
OPS = 1e11
#: A doubling of the chain count that gains less than this share is flat.
PLATEAU = 0.03
#: The sweep stops here whatever it reads.
MAX_CHAINS = 1024


def probe(chains: int, iters: int, reps: int) -> float:
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    def kernel(x_ref, o_ref):
        base = x_ref[...]
        step = base + 1

        def body(_, acc):
            for _ in range(UNROLL):
                acc = jnp.maximum(acc + step, base)
            return acc

        o_ref[...] = jax.lax.fori_loop(0, iters // UNROLL, body, base)

    shape = (chains, 8, 128)
    fn = jax.jit(functools.partial(
        pl.pallas_call, out_shape=jax.ShapeDtypeStruct(shape, jnp.int32))(
            kernel))
    x = jnp.arange(chains * 8 * 128, dtype=jnp.int32).reshape(shape) % 7
    fn(x).block_until_ready()
    best = float("inf")
    for _ in range(reps):
        t0 = time.perf_counter()
        fn(x).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    return (iters // UNROLL) * UNROLL * chains * 2 * 1024 / best


def main() -> int:
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"peaks_probe: needs a TPU; JAX found {dev.platform}",
              file=sys.stderr)
        return 2
    rates, chains, flat = {}, 1, 0
    while chains <= MAX_CHAINS and flat < 2:
        iters = max(int(OPS / (chains * 2 * 1024)) // UNROLL * UNROLL, UNROLL)
        rates[chains] = probe(chains, iters, 5)
        print(f"[peaks_probe] chains={chains}: {rates[chains]:.4e} int32 "
              "ops/s", file=sys.stderr, flush=True)
        if chains > 1:
            gain = rates[chains] / rates[chains // 2] - 1
            flat = flat + 1 if gain < PLATEAU else 0
        chains *= 2
    print(json.dumps({"device_kind": dev.device_kind,
                      "vpu_int32_ops_per_s": max(rates.values()),
                      "by_chains": {str(c): r for c, r in rates.items()}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
