"""Flash-attention device-time microbench: the device-side module span
from a jax.profiler trace, which leaves the host's dispatch out."""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import sys

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import compile_cache, profiling
from horovod_tpu.ops.flash_attention import flash_attention

B, T, H, D = 8, 2048, 16, 128
REPS = 16


def device_ms(make_scan, *args):
    """Run make_scan(*args) (a jitted scan program of REPS repetitions)
    under the profiler; device ms per rep from the module span."""
    log_dir = profiling.capture(
        lambda: jax.block_until_ready(make_scan(*args)), warmup=1, iters=1)
    return profiling.device_time_ms(log_dir, per=REPS)


def bench_fwd(bq, bk, q, k, v):
    @jax.jit
    def many(q, k, v):
        def body(c, _):
            return flash_attention(c, k, v, causal=True, block_q=bq,
                                   block_k=bk), None
        out, _ = lax.scan(body, q, None, length=REPS)
        return out
    return device_ms(many, q, k, v)


def bench_bwd(bq, bk, impl, q, k, v, do):
    def loss(q, k, v):
        return (flash_attention(q, k, v, causal=True, block_q=bq,
                                block_k=bk, bwd_impl=impl)
                .astype(jnp.float32) * do.astype(jnp.float32)).sum()
    gfn = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def many(q, k, v):
        def body(c, _):
            dq, dk, dv = gfn(c, k, v)
            return dq.astype(c.dtype), None
        out, _ = lax.scan(body, q, None, length=REPS)
        return out
    return device_ms(many, q, k, v)


def main():
    compile_cache.enable()
    rng = jax.random.PRNGKey(0)
    kq, kk, kv_, kd = jax.random.split(rng, 4)
    q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
    k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
    v = jax.random.normal(kv_, (B, T, H, D), jnp.bfloat16)
    do = jax.random.normal(kd, (B, T, H, D), jnp.bfloat16)

    causal_area = T * T / 2
    fwd_flops = B * H * 2 * 2 * causal_area * D
    bwd_flops = B * H * 5 * 2 * causal_area * D

    for spec in sys.argv[1:]:
        parts = spec.split(",")
        kind = parts[0]
        if kind == "fwd":
            bq, bk = int(parts[1]), int(parts[2])
            t = bench_fwd(bq, bk, q, k, v)
            print(f"fwd  bq={bq:5d} bk={bk:5d}: {t:7.3f} ms/rep "
                  f"({fwd_flops/t/1e9:6.1f} TF/s useful)", flush=True)
        else:
            bq, bk, impl = int(parts[1]), int(parts[2]), parts[3]
            t = bench_bwd(bq, bk, impl, q, k, v, do)
            print(f"f+b  bq={bq:5d} bk={bk:5d} {impl:13s}: {t:7.3f} ms/rep "
                  f"({(fwd_flops+bwd_flops)/t/1e9:6.1f} TF/s eff)",
                  flush=True)


if __name__ == "__main__":
    main()
