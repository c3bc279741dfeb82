"""Kernel micro-benchmarks.

On this CPU container the Pallas kernels only run in interpret mode (not
representative), so the timed comparison is between the *fused jnp
formulation* the kernel implements and the unfused 4-pass update — the
bandwidth argument the ssca_update kernel encodes.  Derived: modeled
HBM-bytes ratio (the TPU-side speedup bound).
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from benchmarks.common import emit
from repro.core import ssca
from repro.core.schedules import PowerLaw
from repro.kernels import ref


def bench(fn, *args, iters=20):
    fn(*args)  # compile
    t0 = time.time()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.time() - t0) / iters * 1e6


def main() -> None:
    d = 1 << 22   # 4M params ≈ the paper's MLP ×40; CPU-sized
    key = jax.random.key(0)
    ks = jax.random.split(key, 4)
    w, lin, g, beta = (jax.random.normal(k, (d // 128, 128)) for k in ks)
    scal = jnp.asarray([0.5, 0.3, 0.1, 1e-3], jnp.float32)

    fused = jax.jit(ref.ssca_update_2d)
    us_fused = bench(fused, w, lin, g, beta, scal)

    hp = ssca.SSCAHyperParams(tau=0.1, lam=1e-3, rho=PowerLaw(0.5, 1e-9),
                              gamma=PowerLaw(0.3, 1e-9))

    def unfused(w, lin, g, beta):
        st = ssca.SSCAState(step=jnp.asarray(1), lin={"w": lin},
                            beta={"w": beta})
        p, st2 = ssca.server_update(st, {"w": w}, {"w": g}, hp)
        return p["w"], st2.lin["w"], st2.beta["w"]

    us_unfused = bench(jax.jit(unfused), w, lin, g, beta)

    # modeled HBM traffic: fused reads 4 + writes 3 tensors; unfused
    # (14),(13),(16),(4) as separate passes: reads 4+2+2+2, writes 1+1+1+1.
    ratio = (4 + 2 + 2 + 2 + 4) / (4 + 3)
    emit("kernel/ssca_update_fused", us_fused,
         f"modeled_hbm_ratio={ratio:.2f}x")
    emit("kernel/ssca_update_unfused", us_unfused,
         f"cpu_speedup={us_unfused / max(us_fused, 1e-9):.2f}x")

    # flash attention: jnp chunked (the model path the kernel replaces)
    from repro.models import attention
    q = jax.random.normal(ks[0], (1, 2048, 4, 64))
    k = jax.random.normal(ks[1], (1, 2048, 2, 64))
    v = jax.random.normal(ks[2], (1, 2048, 2, 64))
    us_full = bench(jax.jit(lambda a, b, c: attention.attend(a, b, c)),
                    q, k, v)
    us_chunk = bench(jax.jit(
        lambda a, b, c: attention.attend_chunked(a, b, c, chunk=256)),
        q, k, v)
    emit("kernel/attend_full_2k", us_full, "materialized S^2")
    emit("kernel/attend_chunked_2k", us_chunk,
         f"flash-pattern, mem O(S*chunk)")

    # fused count-sketch encode (PR 6): hash + sign + scatter in one
    # pass per member, the client-side cost of the sublinear secure wire
    from repro.fed import sketch as fsk
    comp = fsk.sketch(rows=4, cols=4096, fraction=0.02, keep=256)
    msg = {"w": jax.random.normal(ks[3], (1 << 18,))}
    us_enc = bench(jax.jit(
        lambda m: comp.encode(m, jnp.uint32(1), jnp.uint32(2),
                              jnp.uint32(3))), msg)
    emit("kernel/sketch_encode_256k", us_enc,
         f"rows=4 cols=4096, {1 << 18} elements")

    # grouped masked partial sums (PR 7): G within-group masked sums of
    # M members vs one flat masked sum over S = G·M clients — same total
    # uploads, O(M + G) mask streams per element instead of O(S)
    from repro.kernels import secure_agg as sa
    s_cl, grp, n = 64, 8, 1 << 14
    msgs = jax.random.normal(ks[0], (s_cl, n))
    kd = jnp.asarray([123, 456], jnp.uint32)

    def flat_sum(m):
        return sa.masked_sum_flat(m, kd, 20)

    def grouped_sum(m):
        gm = m.reshape(grp, s_cl // grp, n)
        parts = []
        for gi in range(grp):    # one masked sum per group, G-keyed
            parts.append(sa.masked_ring_partial_sum(
                sa.quantize(gm[gi], 20), kd[0] + jnp.uint32(gi), kd[1],
                0, s_cl // grp))
        gk0, gk1 = sa.group_key_words(kd[0], kd[1])
        return sa.masked_ring_partial_sum(jnp.stack(parts), gk0, gk1,
                                          0, grp)

    us_flat = bench(jax.jit(flat_sum), msgs)
    us_grp = bench(jax.jit(grouped_sum), msgs)
    emit("kernel/masked_sum_flat_64", us_flat, f"S={s_cl} n={n}")
    emit("kernel/masked_sum_grouped_8x8", us_grp,
         f"G={grp} M={s_cl // grp}, "
         f"speedup={us_flat / max(us_grp, 1e-9):.2f}x")


if __name__ == "__main__":
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()
    main()
