"""Kernel micro-bench: fused Pallas cells / flash attention vs jnp reference.

On CPU the Pallas kernels run in INTERPRET mode, so wall-clock here measures
the reference path's cost and validates the kernels' numerics at bench
shapes; the structural win of the fused cell (no HBM round-trip between the
matmuls and the gates) is reported as bytes-moved, which is
hardware-independent.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import ops, ref
from repro.kernels.flash_attention import flash_attention


def _time(f, *a, n=20):
    f(*a)[0].block_until_ready() if isinstance(f(*a), tuple) else \
        f(*a).block_until_ready()
    t0 = time.time()
    for _ in range(n):
        r = f(*a)
        (r[0] if isinstance(r, tuple) else r).block_until_ready()
    return (time.time() - t0) / n * 1e6


def main():
    rows = []
    r = np.random.default_rng(0)
    print("# kernel validation + HBM-traffic model (B=batch, H=hidden)")
    print("kernel,shape,max_err,ref_us,hbm_bytes_fused,hbm_bytes_unfused")
    for B, H in ((64, 64), (256, 128)):
        x = jnp.asarray(r.normal(size=(B, 8)), jnp.float32)
        h = jnp.asarray(r.normal(size=(B, H)), jnp.float32)
        c = jnp.asarray(r.normal(size=(B, H)), jnp.float32)
        p = {"wx": jnp.asarray(r.normal(size=(8, 4 * H)) * .2, jnp.float32),
             "wh": jnp.asarray(r.normal(size=(H, 4 * H)) * .2, jnp.float32),
             "b": jnp.zeros((4 * H,), jnp.float32)}
        h1, c1 = ops.lstm_cell_fused(x, h, c, p)
        h2, c2 = ref.lstm_cell_ref(x, h, c, p["wx"], p["wh"], p["b"])
        err = float(jnp.abs(h1 - h2).max())
        us = _time(lambda: ref.lstm_cell_ref(x, h, c, p["wx"], p["wh"],
                                             p["b"]))
        # fused: read x,h,c,W; write h',c'.  unfused: + (B,4H) preact x3
        fused = 4 * (B * 8 + 2 * B * H + 8 * 4 * H + H * 4 * H + 4 * H
                     + 2 * B * H)
        unfused = fused + 4 * 3 * (B * 4 * H)
        print(f"lstm_cell,B{B}xH{H},{err:.2e},{us:.0f},{fused},{unfused}")
        rows.append(("lstm_cell", err))

    q = jnp.asarray(r.normal(size=(2, 512, 8, 64)), jnp.float32)
    k = jnp.asarray(r.normal(size=(2, 512, 2, 64)), jnp.float32)
    v = jnp.asarray(r.normal(size=(2, 512, 2, 64)), jnp.float32)
    o1 = flash_attention(q, k, v)
    o2 = ref.flash_attention_ref(q, k, v)
    err = float(jnp.abs(o1 - o2).max())
    us = _time(lambda: ref.flash_attention_ref(q, k, v))
    # flash: O(S) memory; ref materializes (B,S,H,S) scores
    s_flash = 4 * (3 * 2 * 512 * 8 * 64 + 2 * 512 * 8 * 64)
    s_ref = s_flash + 4 * (2 * 512 * 8 * 512)
    print(f"flash_attention,B2xS512xH8/2,{err:.2e},{us:.0f},{s_flash},{s_ref}")
    rows.append(("flash_attention", err))
    return rows


if __name__ == "__main__":
    main()
