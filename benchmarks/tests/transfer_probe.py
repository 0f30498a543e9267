#!/usr/bin/env python3
"""Does the runtime's host-to-device copy depend on where the numpy buffer
sits (not a pytest file; half a minute on the chip)?

    python3 benchmarks/tests/transfer_probe.py

At the two sizes the cells hand over every request (a 3.5 MB fold operand,
a 268 MB tree level): ``device_put`` of one buffer at four alignments, of
a freshly allocated buffer each time, and the fetch back.  PERF.md section
6 has the reading it was written for.
"""
import json, time
import numpy as np
import jax

def put_ms(arr, reps):
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        x = jax.device_put(arr); x.block_until_ready()
        out.append(round((time.perf_counter() - t0) * 1000, 3))
        del x
    return out

def fetch_ms(shape, reps):
    x = jax.device_put(np.ones(shape, np.uint32)); x.block_until_ready()
    out = []
    for _ in range(reps):
        y = x + 1; y.block_until_ready()
        t0 = time.perf_counter(); h = np.asarray(y)
        out.append(round((time.perf_counter() - t0) * 1000, 3)); del h, y
    return out

for shape, reps in (((32768, 27), 15), ((4194304, 16), 4)):
    nbytes = int(np.prod(shape)) * 4
    base = np.zeros(nbytes + 8192, np.uint8)
    off = (-base.ctypes.data) % 4096
    for shift in (0, 64, 16, 4):
        a = base[off + shift: off + shift + nbytes].view(np.uint32).reshape(shape)
        a[...] = 7
        print(json.dumps({"shape": shape, "MB": nbytes / 1e6, "address_mod_4096": shift,
                          "device_put_ms": put_ms(a, reps)}), flush=True)
    fresh = []
    for _ in range(reps):
        a = np.full(shape, 7, np.uint32)
        fresh.append((a.ctypes.data % 4096, put_ms(a, 1)[0])); del a
    print(json.dumps({"shape": shape, "fresh_array_each_time(addr_mod_4096, ms)": fresh}), flush=True)
    print(json.dumps({"shape": shape, "fetch_ms": fetch_ms(shape, reps)}), flush=True)
