"""Long-context T-sweep: flash vs full attention fwd+grad on the chip —
device ms (profiler span), tokens/s, and compiled peak temp memory.
Emits a markdown table for docs/long-context.md."""
import os as _os, sys as _sys
_sys.path.insert(0, _os.path.dirname(_os.path.dirname(_os.path.abspath(__file__))))
import functools
import sys

import jax
import jax.numpy as jnp
from jax import lax

from horovod_tpu import compile_cache, profiling
from horovod_tpu.ops.flash_attention import flash_attention
from horovod_tpu.parallel.ring_attention import full_attention

B, H, D = 1, 16, 128
REPS = 8


def device_ms(jfn, *args):
    log_dir = profiling.capture(
        lambda: jax.block_until_ready(jfn(*args)), warmup=1, iters=1)
    return profiling.device_time_ms(log_dir, per=REPS)


def temp_gb(jfn, *args):
    try:
        mem = jfn.lower(*args).compile().memory_analysis()
        return mem.temp_size_in_bytes / 1e9
    except Exception as e:
        return f"? ({type(e).__name__})"


def grad_step(attn_fn):
    def loss(q, k, v, do):
        return (attn_fn(q, k, v).astype(jnp.float32)
                * do.astype(jnp.float32)).sum()
    g = jax.grad(loss, argnums=(0, 1, 2))

    @jax.jit
    def many(q, k, v, do):
        def body(c, _):
            dq, dk, dv = g(c, k, v, do)
            return dq.astype(c.dtype), None
        out, _ = lax.scan(body, q, None, length=REPS)
        return out
    return many


def main():
    compile_cache.enable()
    Ts = [int(a) for a in sys.argv[1:]] or [2048, 4096, 8192, 16384]
    print("| T | impl | fwd+bwd ms | tokens/s (B*T/step) | peak temp GB |")
    print("|---|------|-----------:|--------------------:|-------------:|")
    for T in Ts:
        rng = jax.random.PRNGKey(0)
        kq, kk, kv_, kd = jax.random.split(rng, 4)
        q = jax.random.normal(kq, (B, T, H, D), jnp.bfloat16)
        k = jax.random.normal(kk, (B, T, H, D), jnp.bfloat16)
        v = jax.random.normal(kv_, (B, T, H, D), jnp.bfloat16)
        do = jax.random.normal(kd, (B, T, H, D), jnp.bfloat16)
        for name, fn in (
                ("flash", functools.partial(flash_attention, causal=True)),
                ("full", functools.partial(full_attention, causal=True))):
            try:
                jfn = grad_step(fn)
                mem = temp_gb(jfn, q, k, v, do)
                t = device_ms(jfn, q, k, v, do)
                toks = B * T / (t / 1e3)
                memtxt = (f"{mem:.2f}" if isinstance(mem, float)
                          else str(mem))
                print(f"| {T} | {name} | {t:.2f} | {toks:,.0f} | "
                      f"{memtxt} |", flush=True)
            except Exception as e:
                print(f"| {T} | {name} | OOM/fail "
                      f"({type(e).__name__}: {str(e)[:60]}) | — | — |",
                      flush=True)


if __name__ == "__main__":
    main()
