"""Primitive-cost table for the TPU: element gather vs scatter vs
multi-operand sort vs gather-of-slices vs searchsorted at exchange-relevant
sizes. Each measured inside a length-N scan (one dispatch), with the
result folded into the carry so nothing is dead-code-eliminated.

  python tools/profile_prims.py [N]
"""

import json
import sys
import time

sys.path.insert(0, ".")


def main():
    n = int(sys.argv[1]) if len(sys.argv) > 1 else 32

    import jax
    import jax.numpy as jnp
    import numpy as np

    import shadow_tpu  # noqa: F401  (x64)

    key = jax.random.key(0)
    results = {"backend": jax.default_backend(), "n": n}

    def timed(name, body, *arrs):
        def f(c):
            def step(c, _):
                out = body(*arrs, c)
                return out, None
            c, _ = jax.lax.scan(step, c, None, length=n)
            return c
        g = jax.jit(f)
        c0 = jnp.zeros((), jnp.int64)
        out = g(c0)
        jax.block_until_ready(out)
        t0 = time.perf_counter()
        out = g(c0)
        jax.block_until_ready(out)
        dt = (time.perf_counter() - t0) / n * 1e3
        results[name] = round(dt, 3)
        print(name, round(dt, 3), "ms", flush=True)

    H, Q, O = 10240, 384, 32
    m = H * O

    src64 = jax.random.randint(key, (m,), 0, 1 << 40, dtype=jnp.int64)
    idx_m = jax.random.randint(key, (m,), 0, m, dtype=jnp.int32)
    idx_hk16 = jax.random.randint(key, (H, 16), 0, m, dtype=jnp.int32)
    big2d = jax.random.randint(key, (H, Q), 0, 1 << 40, dtype=jnp.int64)
    sdst = jax.random.randint(key, (m,), 0, H, dtype=jnp.int32)
    sslot = jax.random.randint(key, (m,), 0, Q, dtype=jnp.int32)
    starts = jax.random.randint(key, (H,), 0, m - Q, dtype=jnp.int32)
    keys_m = jax.random.randint(key, (m,), 0, H + 1, dtype=jnp.int32)
    p_ops = [jax.random.randint(jax.random.fold_in(key, i), (m,), 0, 1 << 30,
                                dtype=jnp.int32) for i in range(10)]

    # element gather m from m (i64)
    timed("gather_elem_327k_i64",
          lambda s, i, c: s[(i + c.astype(jnp.int32)) % m].sum() + c, src64, idx_m)
    # element gather [H,16] from m
    timed("gather_elem_164k_i64",
          lambda s, i, c: s[(i + c.astype(jnp.int32)) % m].sum() + c, src64, idx_hk16)
    # scatter m into [H,Q]
    timed("scatter_327k_i64",
          lambda b, d, sl, c: b.at[d, (sl + c.astype(jnp.int32)) % Q]
          .set(jnp.int64(1), mode="drop").sum() + c, big2d, sdst, sslot)
    # gather-of-slices: H slices of length 48 from m
    def gos(s, st, c):
        st = (st + c.astype(jnp.int32)) % (m - 48)
        out = jax.vmap(lambda o: jax.lax.dynamic_slice(s, (o,), (48,)))(st)
        return out.sum() + c
    timed("gather_slices_Hx48_i64", gos, src64, starts)
    # 2-operand sort (key + index)
    timed("sort_2op_327k",
          lambda k2, c: jax.lax.sort((k2 + c.astype(jnp.int32),
                                      jnp.arange(m, dtype=jnp.int32)),
                                     num_keys=1)[1].sum().astype(jnp.int64) + c,
          keys_m)
    # 12-operand sort (key + 64-bit payload split + 8 lanes + aux)
    def sort12(k2, c):
        ops = (k2 + c.astype(jnp.int32),) + tuple(p_ops)
        out = jax.lax.sort(ops, num_keys=1)
        return out[1].sum().astype(jnp.int64) + c
    timed("sort_11op_327k", sort12, keys_m)
    # searchsorted both methods
    hosts = jnp.arange(H, dtype=jnp.int32)
    ks = jnp.sort(keys_m)
    timed("searchsorted_scan",
          lambda s, c: jnp.searchsorted(s, hosts, method="scan").sum()
          .astype(jnp.int64) + c, ks)
    timed("searchsorted_sort",
          lambda s, c: jnp.searchsorted(s, hosts, method="sort").sum()
          .astype(jnp.int64) + c, ks)
    # dense one-hot 16-lane merge into [H,Q] (the delivery-merge pattern)
    lanes = jax.random.randint(key, (H, 16), 0, 1 << 40, dtype=jnp.int64)
    cnt = jax.random.randint(key, (H,), 0, Q - 16, dtype=jnp.int32)
    def dense_merge(b, ln, c):
        qi = jnp.arange(Q, dtype=jnp.int32)[None, :]
        k = qi - cnt[:, None] + (c % 2).astype(jnp.int32)
        take = (k >= 0) & (k < 16)
        picked = jnp.take_along_axis(ln, jnp.clip(k, 0, 15), axis=1)
        return jnp.where(take, picked, b).sum() + c
    timed("dense_merge_16lane_HxQ", dense_merge, big2d, lanes)

    print(json.dumps(results), flush=True)


if __name__ == "__main__":
    main()
