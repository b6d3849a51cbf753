"""Multi-device EP dispatch correctness (8 fake devices via subprocess).

The main pytest process must keep seeing 1 device (jax locks device count
on first init), so every multi-device check runs in a subprocess with
XLA_FLAGS set. One subprocess executes the whole battery to amortize
startup cost.
"""

import os
import subprocess
import sys

import pytest

_SCRIPT = r"""
import os
os.environ['XLA_FLAGS'] = '--xla_force_host_platform_device_count=8'
import sys
sys.path.insert(0, os.environ['REPRO_SRC'])
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from repro.models.sharding import ShardingRules, build_copy_cdf, \
    build_slots_of
from repro.models import moe as MOE

set_mesh = jax.set_mesh
mesh = make_mesh((2, 4), ('data', 'model'))
E, D, F, K = 16, 64, 128, 4
p = MOE.moe_init(jax.random.PRNGKey(0), d=D, f=F, n_experts=E, n_slots=E)
B, S = 4, 8
x = jax.random.normal(jax.random.PRNGKey(1), (B, S, D)).astype(jnp.bfloat16)
y_ref, tally_ref, aux_ref = MOE.moe_layer(p, x, top_k=K, n_experts=E,
                                          rules=None)

def check(tag, y, tally, tol=1e-6):
    err = float(jnp.abs(y_ref.astype(jnp.float32)
                        - y.astype(jnp.float32)).max())
    assert err <= tol, f'{tag}: max err {err}'
    assert np.allclose(np.asarray(tally_ref), np.asarray(tally)), \
        f'{tag}: tally mismatch'
    print(f'{tag}: OK (err={err:.2e})')

# 1. a2a dispatch == dense oracle
rules = ShardingRules(mesh=mesh, dp=('data',), ep=('model',), fsdp=None,
                      capacity_factor=8.0)
with set_mesh(mesh):
    y, t, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules, phase='train'))(p, x)
check('a2a', y, t)

# 2. a2a + FSDP weight sharding
rules_f = ShardingRules(mesh=mesh, dp=('data',), ep=('model',), fsdp='data',
                        capacity_factor=8.0)
with set_mesh(mesh):
    y, t, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules_f, phase='train'))(p, x)
check('a2a+fsdp', y, t)

# 3. replicated decode (all-axes slots, round-robin duplication)
rules_r = ShardingRules(mesh=mesh, dp=('data',), ep=('model',),
                        ep_all=('data', 'model'), fsdp=None,
                        moe_dispatch='replicated', capacity_factor=8.0)
with set_mesh(mesh):
    y, t, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules_r, phase='decode'))(p, x)
check('replicated', y, t)

# 4. expert-TP decode (F sliced over data, partial-sum combine)
rules_tp = ShardingRules(mesh=mesh, dp=('data',), ep=('model',),
                         ep_all=('data', 'model'), fsdp=None,
                         moe_dispatch='replicated', capacity_factor=8.0,
                         decode_expert_tp=True)
with set_mesh(mesh):
    y, t, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules_tp, phase='decode'))(p, x)
check('expert-tp', y, t, tol=2e-2)   # different reduction order (bf16)

# 5. gradients flow through a2a (+aux)
def loss(p, x):
    y, t, a = MOE.moe_layer(p, x, top_k=K, n_experts=E, rules=rules_f,
                            phase='train')
    return (y.astype(jnp.float32) ** 2).mean() + 0.01 * a
with set_mesh(mesh):
    g = jax.jit(jax.grad(loss))(p, x)
for k, v in g.items():
    n = float(jnp.linalg.norm(v.astype(jnp.float32)))
    assert n > 0, f'zero grad for {k}'
print('grads: OK')

# 6. ViBE permutation: migrated weights + slot tables == identity semantics
rng = np.random.default_rng(0)
perm = rng.permutation(E).astype(np.int32)[None, :]
migrated, moved = MOE.apply_placement(
    {k: v[None] for k, v in p.items() if k != 'router'},
    np.arange(E)[None], perm)
p2 = dict(p, **{k: migrated[k][0] for k in ('w1', 'w2', 'w3')})
slots_of, n_copies = build_slots_of(perm, E, E)
with set_mesh(mesh):
    y, t, _ = jax.jit(lambda p2, x: MOE.moe_layer(
        p2, x, top_k=K, n_experts=E, rules=rules,
        slots_of=jnp.asarray(slots_of[0]), n_copies=jnp.asarray(n_copies[0]),
        phase='train'))(p2, x)
check('permuted', y, t)
assert moved > 0

# 7. phantom padding (E=6 experts on 4 EP ranks → 8 slots)
E2 = 6
ns = MOE.n_slots_a2a(E2, 4)
assert ns == 8
p3 = MOE.moe_init(jax.random.PRNGKey(2), d=D, f=F, n_experts=E2, n_slots=ns)
perm3 = MOE.default_perm_a2a(1, E2, 4)
so3, nc3 = build_slots_of(perm3, E2, ns)
y_ref3, t_ref3, _ = MOE.moe_layer(p3, x, top_k=2, n_experts=E2, rules=None,
                                  slots_of=jnp.asarray(so3[0]),
                                  n_copies=jnp.asarray(nc3[0]))
with set_mesh(mesh):
    y3, t3, _ = jax.jit(lambda p3, x: MOE.moe_layer(
        p3, x, top_k=2, n_experts=E2, rules=rules,
        slots_of=jnp.asarray(so3[0]), n_copies=jnp.asarray(nc3[0]),
        phase='train'))(p3, x)
err = float(jnp.abs(y_ref3.astype(jnp.float32) - y3.astype(jnp.float32)).max())
assert err < 1e-6, f'phantom: {err}'
print('phantom padding: OK')

# 8. share-weighted replica routing == dense oracle on both production paths
# 24 slots: experts 0..15 plus replicas of 0..7 with skewed 0.25/0.75 shares
ns8 = 24
perm8 = np.concatenate([np.arange(E), np.arange(8)])[None, :].astype(np.int32)
p8 = {k: (v if k == 'router' else v[perm8[0]]) for k, v in p.items()}
share8 = np.ones((1, ns8))
share8[0, :8] = 0.25
share8[0, 16:] = 0.75
so8, nc8 = build_slots_of(perm8, E, ns8)
cdf8 = build_copy_cdf(perm8, E, ns8, share=share8)
with set_mesh(mesh):
    y8, t8, _ = jax.jit(lambda p8, x: MOE.moe_layer(
        p8, x, top_k=K, n_experts=E, rules=rules,
        slots_of=jnp.asarray(so8[0]), n_copies=jnp.asarray(nc8[0]),
        copy_cdf=jnp.asarray(cdf8[0]), phase='train'))(p8, x)
check('a2a+weighted', y8, t8)
rules8r = ShardingRules(mesh=mesh, dp=('data',), ep=('model',),
                        ep_all=('data', 'model'), fsdp=None,
                        moe_dispatch='replicated', capacity_factor=8.0)
with set_mesh(mesh):
    y8r, t8r, _ = jax.jit(lambda p8, x: MOE.moe_layer(
        p8, x, top_k=K, n_experts=E, rules=rules8r,
        slots_of=jnp.asarray(so8[0]), n_copies=jnp.asarray(nc8[0]),
        copy_cdf=jnp.asarray(cdf8[0]), phase='decode'))(p8, x)
check('replicated+weighted', y8r, t8r)

# 9. capacity drops surface in the tally's final column (a2a, starved cf;
# long sequence so per-device buckets can exceed the rounded-up capacity).
# moe_impl pinned: the ragged default is dropless by construction.
x9 = jax.random.normal(jax.random.PRNGKey(3), (4, 32, D)).astype(jnp.bfloat16)
rules9 = ShardingRules(mesh=mesh, dp=('data',), ep=('model',), fsdp=None,
                       capacity_factor=0.25, moe_impl='capacity')
with set_mesh(mesh):
    _, t9, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules9, phase='train'))(p, x9)
assert float(t9[-1]) > 0, 'starved capacity produced no drops'
assert float(jnp.sum(t9[:E])) == x9.shape[0] * x9.shape[1] * K
print(f'capacity drop column: OK ({float(t9[-1]):.0f} dropped)')

# 10. capacity baseline still == dense oracle at generous cf (checks 1-8 run
# the ragged default; this keeps the legacy bucketed path covered too)
rules10 = ShardingRules(mesh=mesh, dp=('data',), ep=('model',), fsdp=None,
                        capacity_factor=8.0, moe_impl='capacity')
with set_mesh(mesh):
    y10, t10, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules10, phase='train'))(p, x)
check('a2a capacity baseline', y10, t10)

# 11. ragged dispatch is dropless where the same cf starves the buckets:
# full dense-oracle agreement AND a zero drop column on both paths
y9_ref, t9_ref, _ = MOE.moe_layer(p, x9, top_k=K, n_experts=E, rules=None)
rules11 = ShardingRules(mesh=mesh, dp=('data',), ep=('model',), fsdp=None,
                        capacity_factor=0.25, moe_impl='ragged')
with set_mesh(mesh):
    y11, t11, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules11, phase='train'))(p, x9)
err11 = float(jnp.abs(y9_ref.astype(jnp.float32)
                      - y11.astype(jnp.float32)).max())
# bf16 output: summation order differs from the dense combine by
# up to one bf16 ULP on long sequences
assert err11 <= 1e-3, f'ragged@starved-cf: max err {err11}'
assert float(t11[-1]) == 0, 'ragged path reported drops'
assert np.allclose(np.asarray(t11), np.asarray(t9_ref))
rules11r = ShardingRules(mesh=mesh, dp=('data',), ep=('model',),
                         ep_all=('data', 'model'), fsdp=None,
                         moe_dispatch='replicated', capacity_factor=0.25,
                         moe_impl='ragged')
with set_mesh(mesh):
    y11r, t11r, _ = jax.jit(lambda p, x: MOE.moe_layer(
        p, x, top_k=K, n_experts=E, rules=rules11r, phase='decode'))(p, x9)
err11r = float(jnp.abs(y9_ref.astype(jnp.float32)
                       - y11r.astype(jnp.float32)).max())
assert err11r <= 1e-3, f'ragged-replicated@starved-cf: max err {err11r}'
assert float(t11r[-1]) == 0, 'ragged replicated path reported drops'
print('ragged dropless @ starved cf: OK')

print('ALL_EP_CHECKS_PASSED')
"""


@pytest.mark.slow
def test_ep_dispatch_battery():
    env = dict(os.environ)
    env["REPRO_SRC"] = os.path.join(os.path.dirname(__file__), "..", "src")
    env.pop("XLA_FLAGS", None)
    res = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                         capture_output=True, text=True, timeout=900)
    assert "ALL_EP_CHECKS_PASSED" in res.stdout, \
        f"stdout:\n{res.stdout}\nstderr:\n{res.stderr[-3000:]}"
