"""Kernel-vs-oracle smoke (1 device, interpret mode)."""
import numpy as np
import jax.numpy as jnp
from repro.kernels import ops, ref

rng = np.random.RandomState(0)

# halo pack family
u = rng.randn(6, 5, 4).astype(np.float32)
region = (slice(0, 1), slice(0, 5), slice(0, 4))
np.testing.assert_allclose(ops.halo_pack(u, region), ref.halo_pack(jnp.asarray(u), region))
msg = rng.randn(1, 5, 4).astype(np.float32)
np.testing.assert_allclose(ops.halo_unpack_add(u, msg, region),
                           ref.halo_unpack_add(jnp.asarray(u), jnp.asarray(msg), region))
print("halo kernels OK")

# rmsnorm
x = rng.randn(37, 256).astype(np.float32)
w = rng.randn(256).astype(np.float32)
np.testing.assert_allclose(ops.rmsnorm(x, w), ref.rmsnorm(jnp.asarray(x), jnp.asarray(w)), rtol=2e-5)
xb = rng.randn(2, 3, 128).astype(np.float32)
wb = rng.randn(128).astype(np.float32)
np.testing.assert_allclose(ops.rmsnorm(xb, wb, weight_offset=1.0),
                           ref.rmsnorm(jnp.asarray(xb), jnp.asarray(wb), weight_offset=1.0), rtol=2e-5)
print("rmsnorm OK")

# flash attention
B, Hq, Hkv, S, D = 2, 4, 2, 96, 32
q = rng.randn(B, Hq, S, D).astype(np.float32)
k = rng.randn(B, Hkv, S, D).astype(np.float32)
v = rng.randn(B, Hkv, S, D).astype(np.float32)
for kwargs in [dict(causal=True), dict(causal=False), dict(causal=True, window=17),
               dict(causal=True, logit_softcap=20.0)]:
    out = ops.flash_attention(q, k, v, block_q=32, block_k=32, **kwargs)
    want = ref.attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), **kwargs)
    np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
# decode: Sq=1 with q_offset
qd = rng.randn(B, Hq, 1, D).astype(np.float32)
out = ops.flash_attention(qd, k, v, q_offset=S - 1, block_q=1, block_k=32)
want = ref.attention(jnp.asarray(qd), jnp.asarray(k), jnp.asarray(v), q_offset=S - 1)
np.testing.assert_allclose(out, want, rtol=2e-4, atol=2e-5)
print("flash attention OK")

# ssd
B, S, H, P, G, N = 2, 80, 4, 16, 2, 24
x = rng.randn(B, S, H, P).astype(np.float32)
dt = np.abs(rng.randn(B, S, H)).astype(np.float32) * 0.1
A = -np.abs(rng.randn(H)).astype(np.float32)
Bm = rng.randn(B, S, G, N).astype(np.float32)
C = rng.randn(B, S, G, N).astype(np.float32)
y, h = ops.ssd_scan(x, dt, A, Bm, C, chunk=32, return_state=True)
yr, hr = ref.ssd_scan(jnp.asarray(x), jnp.asarray(dt), jnp.asarray(A),
                      jnp.asarray(Bm), jnp.asarray(C), return_state=True)
np.testing.assert_allclose(y, yr, rtol=2e-4, atol=2e-5)
np.testing.assert_allclose(h, hr, rtol=2e-4, atol=2e-5)
print("ssd OK")
print("KERNEL SMOKE PASS")
