"""python tools/host_pieces.py [slots] [table_width] [steps]

What the served step's host path costs piece by piece, on the device JAX
finds (PR 38): each piece warmed once, then timed over 200 repetitions on
`time.perf_counter`; the median, in milliseconds, one JSON line. The pieces
are what the parent's `_dispatch_chunk` / `_read_back` did for one program
(a host key split and unstack, a scalar made into an array, seven arrays put
on the device, four arrays copied back) and what this PR's path does instead
(one buffer put, one vector copied back). Sizes default to the
`mistral7b-reason-sat` cell's: 32 slots, 28 table columns, 8 steps.
"""
import json
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

REPEAT = 200


def median_ms(fn) -> float:
    fn()
    times = []
    for _ in range(REPEAT):
        t0 = time.perf_counter()
        fn()
        times.append(time.perf_counter() - t0)
    return float(np.median(times) * 1e3)


def main(slots=32, width=28, steps=8) -> dict:
    key = jax.random.PRNGKey(0)
    small = [np.arange(slots, dtype=np.int32) for _ in range(5)]
    tables = [np.zeros((slots, width), np.int32) for _ in range(2)]
    seven = small + tables
    packed_in = np.concatenate([a.reshape(-1) for a in seven])

    @jax.jit
    def outputs(x):
        # fresh device arrays a program's end would hand back: tokens,
        # lengths, done flags, a first token — and the same packed
        return (jnp.tile(x[:slots, None], (1, steps)), x[:slots],
                x[:slots] > 3, x[:1],
                jnp.concatenate([jnp.tile(x[:slots], steps), x[:2 * slots],
                                 x[:1]]))

    dev = jax.device_put(packed_in)

    def four_copies():
        outs = outputs(dev)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        for x in outs[:4]:
            np.asarray(x)
        return time.perf_counter() - t0

    def one_copy():
        outs = outputs(dev)
        jax.block_until_ready(outs)
        t0 = time.perf_counter()
        np.asarray(outs[4])
        return time.perf_counter() - t0

    def split():
        nonlocal key
        key, _ = jax.random.split(key)

    def copies_ms(fn) -> float:
        fn()
        return float(np.median([fn() for _ in range(REPEAT)]) * 1e3)

    res = {
        "device": jax.devices()[0].device_kind,
        "slots": slots, "table_width": width, "steps": steps,
        "split_unstack_ms": median_ms(split),
        "scalar_asarray_ms": median_ms(
            lambda: jnp.asarray(0.7, jnp.float32)),
        "seven_asarray_ms": median_ms(
            lambda: [jnp.asarray(a) for a in seven]),
        "four_np_asarray_ms": copies_ms(four_copies),
        "one_device_put_packed_ms": median_ms(
            lambda: jax.device_put(packed_in)),
        "one_np_asarray_packed_ms": copies_ms(one_copy),
        "packed_in_bytes": int(packed_in.nbytes),
    }
    res["parent_pieces_ms"] = (res["split_unstack_ms"]
                               + 2 * res["scalar_asarray_ms"]
                               + res["seven_asarray_ms"]
                               + res["four_np_asarray_ms"])
    return res


if __name__ == "__main__":
    print(json.dumps(main(*map(int, sys.argv[1:]))), flush=True)
