#!/usr/bin/env python3
"""Read the VPU's 32-bit elementwise op/s on the chip, to check the ceiling
in ``bench/peaks.json``.

    python bench/calibrate_vpu.py            # on a TPU

A Pallas kernel keeps ``vregs`` independent (8, 128) float32 tiles in
registers and applies ``x = min(x + a, b)`` to each of them ``iters`` times:
two VPU ops per element per iteration, no memory traffic inside the loop,
and as many independent chains as there are tiles, so the issue slots and
not the latency bound the rate. Each variant is timed on the host clock over
repeated calls ending in ``block_until_ready`` (each call runs tens of
milliseconds). The readings must stay at or under ``vpu_ops_per_s`` of the
device's entry in ``peaks.json``; the script exits 1 when one does not,
after printing every reading.
"""
from __future__ import annotations

import functools
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
#: loop steps written out per iteration of the kernel's loop (Mosaic takes
#: no ``unroll`` but 1 or all)
UNROLL = 8


def _kernel(a_ref, b_ref, o_ref, *, iters):
    import jax
    import jax.numpy as jnp

    a, b = a_ref[...], b_ref[...]

    def body(_, x):
        for _ in range(UNROLL):
            x = jnp.minimum(x + a, b)
        return x

    o_ref[...] = jax.lax.fori_loop(0, iters // UNROLL, body,
                                   jnp.zeros_like(a))


@functools.lru_cache(maxsize=None)
def _program(vregs: int, iters: int, interpret: bool):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    shape = (8 * vregs, 128)
    call = pl.pallas_call(functools.partial(_kernel, iters=iters),
                          out_shape=jax.ShapeDtypeStruct(shape, jnp.float32),
                          name="vpu_min_add", interpret=interpret)
    return jax.jit(call), shape


def reading(vregs: int, iters: int, calls: int = 10,
            interpret: bool = False) -> float:
    """Elementwise ops per second of one variant (2 ops per element per
    iteration)."""
    import jax
    import jax.numpy as jnp

    fn, shape = _program(vregs, iters, interpret)
    a = jnp.full(shape, 1.0, jnp.float32)
    b = jnp.full(shape, float(iters) * 4, jnp.float32)
    fn(a, b).block_until_ready()                    # compile
    t0 = time.perf_counter()
    for _ in range(calls):
        out = fn(a, b)
    out.block_until_ready()
    dt = time.perf_counter() - t0
    if float(out[0, 0]) != float(iters):            # every add happened
        raise RuntimeError(f"calibration kernel read {float(out[0, 0])}, "
                           f"expected {iters}")
    return 2.0 * shape[0] * shape[1] * iters * calls / dt


def main() -> int:
    import jax

    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"no TPU: JAX runs on {dev.platform}", file=sys.stderr)
        return 2
    with open(os.path.join(HERE, "peaks.json")) as f:
        peaks = json.load(f)
    if dev.device_kind not in peaks:
        print(f"no peaks for device kind {dev.device_kind!r}", file=sys.stderr)
        return 2
    ceiling = peaks[dev.device_kind]["vpu_ops_per_s"]["value"]
    worst = 0.0
    for vregs in (4, 8, 16, 24):
        ops = reading(vregs, iters=1 << 20)
        worst = max(worst, ops)
        print(f"vregs={vregs}: {ops:.6e} op/s "
              f"({100 * ops / ceiling:.2f}% of {ceiling:.6e})")
    print(json.dumps({"device_kind": dev.device_kind,
                      "max_reading_ops_per_s": worst,
                      "ceiling_ops_per_s": ceiling}))
    return 0 if worst <= ceiling else 1


if __name__ == "__main__":
    sys.exit(main())
