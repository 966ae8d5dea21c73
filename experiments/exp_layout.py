"""Layout experiment: fe_mul throughput, batch-first (B,20) vs batch-last (20,B).

Hypothesis: minor dims of 20/39 pad to 128 lanes on TPU -> ~15-30% VPU
utilization; putting the batch on the minor (lane) dim should win big.
"""
import time
import numpy as np
import jax
import jax.numpy as jnp
from functools import partial

import sys
sys.path.insert(0, "/root/repo")
from stellar_core_tpu.ops import field as F

B = 8192
NITER = 200

# ---------------- batch-first (current) ----------------

@jax.jit
def chain_first(x, y):
    def body(i, x):
        return F.fe_mul(x, y)
    return jax.lax.fori_loop(0, NITER, body, x)

# ---------------- batch-last ----------------

NLIMBS, LB, MASK, FOLD = F.NLIMBS, F.LIMB_BITS, F.LIMB_MASK, F.FOLD

def carry_round_T(c):
    lo = c & MASK
    hi = c >> LB
    wrapped = jnp.concatenate([hi[19:20] * FOLD, hi[:19]], axis=0)
    return lo + wrapped

def fe_mul_T(a, b):
    # columns: c[k] = sum_{i+j=k} a_i b_j  -> (39, B)
    parts = []
    zb = jnp.zeros((1, a.shape[-1]), jnp.int32)
    acc = jnp.zeros((39, a.shape[-1]), jnp.int32)
    # accumulate via padded adds; static slices
    terms = []
    for i in range(NLIMBS):
        p = a[i][None, :] * b          # (20, B)
        pad_lo = jnp.zeros((i, a.shape[-1]), jnp.int32)
        pad_hi = jnp.zeros((19 - i, a.shape[-1]), jnp.int32)
        terms.append(jnp.concatenate([pad_lo, p, pad_hi], axis=0))
    c = sum(terms)
    # widening carry round
    lo = c & MASK
    hi = c >> LB
    z1 = jnp.zeros((1, a.shape[-1]), jnp.int32)
    c = jnp.concatenate([lo, z1], axis=0) + jnp.concatenate([z1, hi], axis=0)
    low = c[:NLIMBS] + FOLD * c[NLIMBS:]
    for _ in range(2):
        low = carry_round_T(low)
    return low

@jax.jit
def chain_last(x, y):
    def body(i, x):
        return fe_mul_T(x, y)
    return jax.lax.fori_loop(0, NITER, body, x)


def bench(fn, *args, tag=""):
    t0 = time.perf_counter()
    r = fn(*args)
    r.block_until_ready()
    tc = time.perf_counter() - t0
    best = 1e9
    for _ in range(5):
        t0 = time.perf_counter()
        fn(*args).block_until_ready()
        best = min(best, time.perf_counter() - t0)
    per_mul_ns = best / NITER / B * 1e9
    print(f"{tag}: compile {tc:.1f}s, best {best*1e3:.2f}ms for {NITER} muls "
          f"x {B} batch -> {per_mul_ns:.2f} ns/fe_mul/item", flush=True)
    return best


def main():
    rng = np.random.default_rng(0)
    xf_np = rng.integers(0, 8191, (B, NLIMBS), dtype=np.int32)
    yf_np = rng.integers(0, 8191, (B, NLIMBS), dtype=np.int32)
    xf = jnp.asarray(xf_np)
    yf = jnp.asarray(yf_np)
    xl = jnp.asarray(np.ascontiguousarray(xf_np.T))
    yl = jnp.asarray(np.ascontiguousarray(yf_np.T))

    # correctness cross-check (jitted: eager dispatch pays a host round
    # trip per op)
    chk_a = jax.jit(lambda x, y: F.fe_freeze(F.fe_mul(x, y)))
    chk_b = jax.jit(lambda x, y: F.fe_freeze(fe_mul_T(x, y).T))
    a = np.asarray(chk_a(xf, yf))
    b = np.asarray(chk_b(xl, yl))
    assert np.array_equal(a, b), "mismatch!"
    print("correctness ok", flush=True)

    t_first = bench(chain_first, xf, yf, tag="batch-first (B,20)")
    t_last = bench(chain_last, xl, yl, tag="batch-last (20,B)")
    print(f"speedup: {t_first / t_last:.2f}x")


if __name__ == "__main__":
    main()
