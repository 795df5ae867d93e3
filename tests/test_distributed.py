"""Distribution correctness on an 8-device child process mesh.

These spawn subprocesses with XLA_FLAGS=8 fake devices so the main pytest
process keeps its single-device view (per the dry-run isolation rule).
"""
import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_child(code: str) -> dict:
    env = dict(os.environ,
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    out = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    return json.loads(out.stdout.strip().splitlines()[-1])


CHILD_PRELUDE = """
import json
import jax, jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P
from repro.launch.mesh import make_mesh
from jax import shard_map
"""


def test_tiled_allreduce_variants_match():
    r = run_child(CHILD_PRELUDE + """
import functools
from repro.core.tiled_allreduce import (tiled_matmul_allreduce,
    single_matmul_allreduce, ring_matmul_allreduce,
    tiled_matmul_reducescatter)
mesh = make_mesh((2,4), ('data','model'))
rng = np.random.default_rng(0)
x = jnp.asarray(rng.normal(size=(64, 32)), jnp.float32)
w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
ref = x @ w
errs = {}
for name, fn in [('single', single_matmul_allreduce),
                 ('tiled', tiled_matmul_allreduce),
                 ('ring', ring_matmul_allreduce)]:
    f = shard_map(functools.partial(fn, axis_name='model'), mesh=mesh,
        in_specs=(P(None,'model'), P('model',None)),
        out_specs=P(None,None), check_vma=False)
    errs[name] = float(jnp.max(jnp.abs(jax.jit(f)(x, w) - ref)))
# reduce-scatter variant: rows come back chunk-block-scattered, so
# compare with n_chunks=1 where the global ordering is the identity
f = shard_map(functools.partial(tiled_matmul_reducescatter,
    axis_name='model', n_chunks=1), mesh=mesh,
    in_specs=(P(None,'model'), P('model',None)),
    out_specs=P('model',None), check_vma=False)
errs['rs'] = float(jnp.max(jnp.abs(jax.jit(f)(x, w) - ref)))
print(json.dumps(errs))
""")
    for name, err in r.items():
        assert err < 1e-4, (name, err)


def test_tiled_allreduce_emits_multiple_collectives():
    """T3 structure check: tiled mode has n_chunks collectives vs 1."""
    r = run_child(CHILD_PRELUDE + """
import functools
from repro.core.tiled_allreduce import (ring_matmul_allreduce,
                                        single_matmul_allreduce)
from repro.analysis.hlo import analyze_hlo_text
mesh = make_mesh((8,), ('model',))
sds = jax.ShapeDtypeStruct
counts = {}
for name, fn, kw in [('single', single_matmul_allreduce, {}),
                     ('ring', ring_matmul_allreduce, dict(n_chunks=4))]:
    f = shard_map(functools.partial(fn, axis_name='model', **kw),
        mesh=mesh, in_specs=(P(None,'model'), P('model',None)),
        out_specs=P(None,None), check_vma=False)
    c = jax.jit(f).lower(sds((128, 64), jnp.float32),
                         sds((64, 32), jnp.float32)).compile()
    cost = analyze_hlo_text(c.as_text())
    n = sum(n for _, _, n in cost.top_collectives)
    counts[name] = n
print(json.dumps(counts))
""")
    # NOTE: XLA's all-reduce combiner merges adjacent small psums, so the
    # plain `tiled` mode can collapse back to one op at toy sizes; the
    # ring variant's collective-permutes are structurally un-mergeable
    # (data dependence through the accumulator), guaranteeing overlap.
    assert r["single"] >= 1
    assert r["ring"] >= 4 * r["single"]


def test_context_parallel_decode_matches_oracle():
    r = run_child(CHILD_PRELUDE + """
from repro.core.distributed_decode import context_parallel_decode
from repro.kernels.fastattn.ref import decode_reference
mesh = make_mesh((2,4), ('data','model'))
rng = np.random.default_rng(0)
B,Hq,Hkv,S,D = 4, 8, 2, 256, 32
q = jnp.asarray(rng.normal(size=(B,Hq,1,D)), jnp.float32)
k = jnp.asarray(rng.normal(size=(B,Hkv,S,D)), jnp.float32)
v = jnp.asarray(rng.normal(size=(B,Hkv,S,D)), jnp.float32)
kvlen = jnp.asarray([256, 100, 7, 200], jnp.int32)
errs = {}
ref = decode_reference(q,k,v,kvlen)[:,:,0]
out = context_parallel_decode(mesh, q[:,:,0], k, v, kvlen)
errs['plain'] = float(jnp.max(jnp.abs(out-ref)))
ref2 = decode_reference(q,k,v,kvlen,window=64)[:,:,0]
out2 = context_parallel_decode(mesh, q[:,:,0], k, v, kvlen, window=64)
errs['window'] = float(jnp.max(jnp.abs(out2-ref2)))
print(json.dumps(errs))
""")
    for name, err in r.items():
        assert err < 1e-4, (name, err)


def test_sharded_model_forward_matches_single_device():
    """A reduced arch under the production rule table on a (2,4) mesh must
    produce the same logits as unsharded execution."""
    r = run_child(CHILD_PRELUDE + """
from repro.config import get_model_config, reduce_for_smoke, ParallelConfig
from repro.models import build_model
from repro.sharding.rules import axis_rules, param_sharding_tree
cfg = reduce_for_smoke(get_model_config('qwen2.5-32b'))
mesh = make_mesh((2,4), ('data','model'))
model = build_model(cfg, ParallelConfig(data=2, model=4, remat='none'))
toks = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0,
                          cfg.vocab_size)
params = model.init(jax.random.PRNGKey(0))
base = model.apply(params, toks)           # single-device semantics
with axis_rules(mesh=mesh):
    sh = param_sharding_tree(model.logical(), mesh)
    params_s = jax.device_put(params, sh)
    with mesh:
        out = jax.jit(model.apply)(params_s, toks)
err = float(jnp.max(jnp.abs(out.astype(jnp.float32)
                            - base.astype(jnp.float32))))
print(json.dumps({'err': err}))
""")
    assert r["err"] < 1e-3


def test_compressed_psum_error_feedback():
    """int8+EF all-reduce: one-step error bounded, residual carries the
    quantization error so the running average converges."""
    r = run_child(CHILD_PRELUDE + """
from repro.training.compression import compressed_psum
mesh = make_mesh((8,), ('data',))
rng = np.random.default_rng(0)
g_all = jnp.asarray(rng.normal(size=(8, 64, 32)), jnp.float32)
true_mean = jnp.mean(g_all, axis=0)

def body(g):
    g = g[0]                                 # (64, 32) local shard
    res = jnp.zeros_like(g)
    red, res = compressed_psum(g, res, 'data')
    return red[None], res[None]

f = jax.jit(shard_map(body, mesh=mesh,
    in_specs=P('data', None, None),
    out_specs=(P(None, None, None), P('data', None, None)),
    check_vma=False))
red, res = f(g_all)
rel = float(jnp.max(jnp.abs(red[0] - true_mean))) / \
    float(jnp.max(jnp.abs(true_mean)))
# EF invariant: applied + residual == exact (per device, pre-reduction)
print(json.dumps({'rel': rel}))
""")
    assert r["rel"] < 0.15   # one-shot int8 error (EF recovers it over steps)


def test_chunk_sizes_alignment_contract():
    """Every chunk -- including the trailing remainder -- must respect
    ``align``.  The old code appended a raw remainder, e.g.
    chunk_sizes(10, 2, 1.0, 4) -> [4, 6]: the 6 mis-split the ring's
    per-device pieces and psum_scatter's axis chunks."""
    from repro.core.tiled_allreduce import chunk_sizes

    # the regression shape now refuses instead of mis-aligning
    with pytest.raises(ValueError):
        chunk_sizes(10, 2, 1.0, align=4)
    with pytest.raises(ValueError):
        chunk_sizes(0, 4)
    for total, n, frac, align in [(16, 2, 1.0, 4), (64, 4, 0.5, 8),
                                  (8, 4, 0.5, 4), (128, 4, 0.5, 1),
                                  (12, 5, 0.25, 4), (4, 4, 0.5, 4),
                                  (96, 3, 0.5, 32)]:
        sizes = chunk_sizes(total, n, frac, align=align)
        assert sum(sizes) == total, (total, n, frac, align, sizes)
        assert all(s > 0 for s in sizes), sizes
        assert all(s % align == 0 for s in sizes), (align, sizes)
        assert len(sizes) <= n
    # first-chunk shrinking still happens when there is room
    sizes = chunk_sizes(128, 4, 0.5, align=1)
    assert sizes[0] < sizes[1]


def test_allreduce_variants_match_on_unaligned_rows():
    """Equivalence on row counts that divide NEITHER the chunk count nor
    the axis size, across 2- and 4-way meshes: the ring variant pads to
    a multiple of the axis size internally and slices the pad back off;
    the reduce-scatter variant refuses rather than mis-splitting."""
    r = run_child(CHILD_PRELUDE + """
import functools
from repro.core.tiled_allreduce import (tiled_matmul_allreduce,
    single_matmul_allreduce, ring_matmul_allreduce,
    tiled_matmul_reducescatter, matmul_allreduce)
rng = np.random.default_rng(0)
w = jnp.asarray(rng.normal(size=(32, 16)), jnp.float32)
errs = {}
for ways in (2, 4):
    mesh = make_mesh((ways,), ('model',))
    for t in (37, 50):
        x = jnp.asarray(rng.normal(size=(t, 32)), jnp.float32)
        ref = x @ w
        for name, fn in [('single', single_matmul_allreduce),
                         ('tiled', tiled_matmul_allreduce),
                         ('ring', ring_matmul_allreduce)]:
            f = shard_map(functools.partial(fn, axis_name='model'),
                mesh=mesh, in_specs=(P(None,'model'), P('model',None)),
                out_specs=P(None,None), check_vma=False)
            errs[f'{name}-{ways}w-{t}'] = float(jnp.max(jnp.abs(
                jax.jit(f)(x, w) - ref)))
        # dispatcher parity on the same shapes
        for mode in ('tiled', 'single'):
            f = shard_map(functools.partial(matmul_allreduce,
                axis_name='model', mode=mode), mesh=mesh,
                in_specs=(P(None,'model'), P('model',None)),
                out_specs=P(None,None), check_vma=False)
            errs[f'dispatch-{mode}-{ways}w-{t}'] = float(jnp.max(jnp.abs(
                jax.jit(f)(x, w) - ref)))
    # reduce-scatter refuses axis-indivisible rows instead of corrupting
    x = jnp.asarray(rng.normal(size=(37, 32)), jnp.float32)
    f = shard_map(functools.partial(tiled_matmul_reducescatter,
        axis_name='model'), mesh=mesh,
        in_specs=(P(None,'model'), P('model',None)),
        out_specs=P('model',None), check_vma=False)
    try:
        jax.jit(f)(x, w)
        errs[f'rs-guard-{ways}w'] = 1e9
    except ValueError:
        errs[f'rs-guard-{ways}w'] = 0.0
print(json.dumps(errs))
""")
    for name, err in r.items():
        assert err < 1e-4, (name, err)
