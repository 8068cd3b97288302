import json, sys, time, functools
sys.path.insert(0, "/root/repo")
import jax, jax.numpy as jnp
from distributed_pipeline_tpu.ops.flash_attention import flash_attention

def drain(out):
    float(jax.device_get(jnp.sum(out[0] if isinstance(out, tuple) else out).astype(jnp.float32)))

def chain_total(fn_body, reps, *args):
    @jax.jit
    def chain(q, k, v):
        return jax.lax.fori_loop(0, reps, lambda _, c: fn_body(c, k, v), q)
    drain(chain(*args))
    t0 = time.perf_counter(); drain(chain(*args)); return time.perf_counter() - t0

bq, bk = (int(sys.argv[1]), int(sys.argv[2])) if len(sys.argv) > 2 else (1024, 1024)
for (B, H, L, Dh) in [(2, 12, 4096, 64), (2, 12, 8192, 64)]:
    kq, kk, kv = jax.random.split(jax.random.PRNGKey(0), 3)
    q = jax.random.normal(kq, (B, H, L, Dh), jnp.bfloat16)
    k = jax.random.normal(kk, (B, H, L, Dh), jnp.bfloat16)
    v = jax.random.normal(kv, (B, H, L, Dh), jnp.bfloat16)
    import os
    mask = (jnp.ones((B, L), jnp.int32) if os.environ.get("WITH_MASK")
            else None)
    fwd_body = lambda c, kk_, vv_: flash_attention(c, kk_, vv_, mask, True, bq, bk)
    g = jax.grad(lambda a,b,c_: jnp.sum(flash_attention(a,b,c_,mask,True,bq,bk).astype(jnp.float32)**2), argnums=(0,1,2))
    def bwd_body(c, kk_, vv_):
        dq, dk, dv = g(c, kk_, vv_)
        return (c + 1e-30*dq + 1e-30*dk + 1e-30*dv).astype(c.dtype)
    # chain lengths long enough that the per-call dispatch overhead is a
    # small share of the differenced signal; min-of-2 marginals
    for name, body, lo, hi in [("fwd", fwd_body, 64, 320),
                               ("fwdbwd", bwd_body, 16, 80)]:
        margs = []
        for _ in range(2):
            t_lo = chain_total(body, lo, q, k, v)
            t_hi = chain_total(body, hi, q, k, v)
            margs.append((t_hi - t_lo) / (hi - lo) * 1e3)
        print(json.dumps({"shape": f"L{L}", "block": [bq, bk], "kind": name,
                          "per_call_ms": round(min(margs), 3),
                          "all": [round(m, 3) for m in margs]}), flush=True)
