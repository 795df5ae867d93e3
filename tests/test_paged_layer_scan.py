"""The paged step programs carry the layer-stacked KV pools through the
layer scan and write and read one layer of them by index.

After one prefill chunk and a few decode steps, the logits and every
pool leaf must equal a plain per-layer loop over one-layer pools: the
same model built with ``scan_layers=False``, its stacked parameters and
pools split layer by layer.  Covered: a one-kind unit scanned three
times (qwen2.5: GQA, QKV bias) and a two-kind unit (gemma2: sliding
window + full attention, logit softcap) scanned twice; the gather
reference and the Pallas kernels in interpret mode, on (page_size, D)
pages and on the transposed pages of a 128-row page with a narrow head;
tp=1 here and tp=2 on fake CPU devices in a child process.
"""
import dataclasses
import functools
import json
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import ParallelConfig, get_model_config, reduce_for_smoke
from repro.models import build_model

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

# (arch, layers): qwen2.5 scans a one-kind unit 3 times, gemma2 its
# (attn_local, attn) unit twice
ARCHS = [("qwen2.5-32b", 3), ("gemma2-2b", 4)]
DECODE_STEPS = 3


def _models(arch, layers):
    cfg = dataclasses.replace(reduce_for_smoke(get_model_config(arch)),
                              num_layers=layers)
    stacked = build_model(cfg, ParallelConfig(remat="none"))
    flat = build_model(cfg, ParallelConfig(remat="none", scan_layers=False))
    assert any(reps > 1 for _, reps in stacked.segs)
    assert all(reps == 1 and len(unit) == 1 for unit, reps in flat.segs)
    return cfg, stacked, flat


def split_layers(stacked, tree):
    """A stacked model's params or pools as the flat model's tree: block
    j of the flat model is unit member i of repeat r."""
    out = {k: v for k, v in tree.items() if not k.startswith("seg")}
    j = 0
    for si, (unit, reps) in enumerate(stacked.segs):
        for r in range(reps):
            for i in range(len(unit)):
                leaf = tree[f"seg{si}"][f"u{i}"]
                if reps > 1:
                    leaf = jax.tree.map(lambda a, r=r: a[r], leaf)
                out[f"seg{j}"] = {"u0": leaf}
                j += 1
    return out


def _inputs(cfg, num_pages, page_size):
    """Two sequences: one prompt chunk from position 0 (all 8 rows
    valid) and one from position 2 * page_size - 3 (5 valid rows, 3
    padding rows that go to the scratch page), then decode steps."""
    rng = np.random.default_rng(0)
    n_kv = 4
    table = rng.permutation(np.arange(1, num_pages))[:2 * n_kv]
    return dict(
        chunk=jnp.asarray(rng.integers(0, cfg.vocab_size, (2, 8)),
                          jnp.int32),
        table=jnp.asarray(table.reshape(2, n_kv), jnp.int32),
        pos_start=jnp.asarray([0, 2 * page_size - 3], jnp.int32),
        n_valid=jnp.asarray([8, 5], jnp.int32),
        tokens=jnp.asarray(rng.integers(0, cfg.vocab_size,
                                        (DECODE_STEPS, 2)), jnp.int32))


def serve(model, params, pools, inp, impl, wrap=lambda f: f):
    """One prefill chunk, then DECODE_STEPS decode steps; returns the
    logits of every launch and the pools."""
    pre = wrap(jax.jit(functools.partial(model.prefill_chunk_paged,
                                         impl=impl)))
    dec = wrap(jax.jit(functools.partial(model.decode_step_paged,
                                         impl=impl)))
    logits, pools = pre(params, inp["chunk"], pools, inp["table"],
                        inp["pos_start"], inp["n_valid"])
    out = [logits]
    pos = inp["pos_start"] + inp["n_valid"]
    for t in range(DECODE_STEPS):
        lg, pools = dec(params, inp["tokens"][t], pools, inp["table"],
                        pos + t)
        out.append(lg)
    return out, pools


def compare(arch, layers, impl, page_size, tp=1):
    """Stacked (layer scan, pools carried) against flat (per-layer loop
    over one-layer pools).  Returns the largest absolute difference of
    the logits and of the pools."""
    cfg, stacked, flat = _models(arch, layers)
    num_pages = 12
    params = stacked.init(jax.random.PRNGKey(0))
    # random pools: the pages a chunk does not write are read as context
    pools = jax.tree.map(
        lambda a: jax.random.normal(jax.random.PRNGKey(1), a.shape, a.dtype),
        stacked.init_paged_cache(num_pages, page_size))
    inp = _inputs(cfg, num_pages, page_size)
    wrap, put = (lambda f: f), (lambda model, tree: tree)
    if tp > 1:
        from repro.launch.mesh import make_mesh
        from repro.sharding.tp import plan_tp, tp_context
        plan = plan_tp(cfg, tp, page_size)
        mesh = make_mesh(plan.mesh_shape, plan.axes)

        def wrap(f):
            def run(*args):
                with tp_context(mesh, plan):
                    return f(*args)
            return run

        def put(model, tree):
            return jax.device_put(
                tree, model.paged_cache_sharding(mesh, num_pages, page_size))

    got, got_pools = serve(stacked, params, put(stacked, pools), inp, impl,
                           wrap)
    want, want_pools = serve(flat, split_layers(stacked, params),
                             put(flat, split_layers(stacked, pools)), inp,
                             impl, wrap)

    def gap(a, b):
        return max(float(jnp.max(jnp.abs(x - y)))
                   for x, y in zip(jax.tree.leaves(a), jax.tree.leaves(b)))

    return {"logits": gap(got, want),
            "pools": gap(split_layers(stacked, got_pools), want_pools),
            "pools_moved": gap(split_layers(stacked, got_pools),
                               split_layers(stacked, pools))}


@pytest.mark.parametrize("impl,page_size",
                         [("paged_reference", 16), ("paged_interpret", 16),
                          ("paged_interpret", 128)],
                         ids=["reference", "kernel", "kernel-pages-t"])
@pytest.mark.parametrize("arch,layers", ARCHS, ids=[a for a, _ in ARCHS])
def test_stacked_pools_match_per_layer_loop(arch, layers, impl, page_size):
    r = compare(arch, layers, impl, page_size)
    assert r["pools_moved"] > 0.1          # the steps did write the pools
    assert r["pools"] <= 1e-6, r
    assert r["logits"] <= 1e-5, r


def test_stacked_pools_match_per_layer_loop_tp2():
    """tp=2 on fake CPU devices (2 kv-head groups of the smoke configs):
    the shard_map bodies write and read the stacked pools by layer."""
    code = f"""
import json, sys
sys.path.insert(0, {os.path.join(ROOT, 'tests')!r})
import test_paged_layer_scan as t
import jax
out = {{'devices': jax.device_count()}}
for arch, layers in t.ARCHS:
    out[arch] = t.compare(arch, layers, 'paged_reference', 16, tp=2)
print(json.dumps(out))
"""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=2",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=900)
    assert res.returncode == 0, res.stderr[-3000:]
    out = json.loads(res.stdout.strip().splitlines()[-1])
    assert out["devices"] == 2
    for arch, _ in ARCHS:
        assert out[arch]["pools_moved"] > 0.1
        assert out[arch]["pools"] <= 1e-6, out
        assert out[arch]["logits"] <= 1e-5, out
