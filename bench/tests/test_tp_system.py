"""The harness's tensor-parallel engine: a mix's ``tp`` reaches
``ServeConfig``; at ``tp`` 4 the weights are made replicated on the
engine's mesh (its own placement then moves nothing) and the page pools
sharded over it, and the greedy logits are those of the one-device
engine.  Mixes without ``tp`` build what they always built.

The four-device case runs in a child process on four virtual CPU
devices, so this process keeps its one-device view."""
import dataclasses
import json
import os
import subprocess
import sys

import jax
import pytest

from conftest import BENCH, ENGINE, TINY

CHILD = """
import json, sys
sys.path[:0] = [BENCH, TESTS]
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import NamedSharding, PartitionSpec
from conftest import ENGINE, TINY
import system
from repro.config import ParallelConfig
from repro.models import build_model
from repro.serving.core import EngineCore

out = {}
for tp in (1, 4):
    mcfg = system.model_config(TINY)
    model = build_model(mcfg, ParallelConfig())
    serve = system.serve_config(dict(ENGINE, tp=tp))
    mesh = system.tp_mesh(mcfg, serve)
    rep = None if mesh is None else NamedSharding(mesh, PartitionSpec())
    params = system.make_params(model, TINY, 11, rep)
    core = EngineCore(model, params, mcfg, serve)
    system.fill_pools(core)
    if tp == 4:
        mgr = core.mgr
        want = model.paged_cache_sharding(core.tp_mesh, mgr.num_pages,
                                          mgr.page_size)
        pools = jax.tree.leaves(core.pools)
        out.update(
            mesh=core.tp_mesh.devices.size,
            same_mesh=mesh == core.tp_mesh,
            params_replicated=all(a.sharding == rep
                                  for a in jax.tree.leaves(params)),
            params_not_moved=all(a is b for a, b in zip(
                jax.tree.leaves(core.params), jax.tree.leaves(params))),
            pools_sharded=all(a.sharding == b for a, b in zip(
                pools, jax.tree.leaves(want))),
            pool_quarters=all(
                max(s.data.size for s in a.addressable_shards) * 4 == a.size
                for a in pools))
    # one greedy prefill chunk through the engine's own program
    toks = np.random.default_rng(5).integers(0, TINY["vocab_size"], (1, 32),
                                             dtype=np.int32)
    table = np.arange(1, 1 + core.mgr.max_pages_per_seq, dtype=np.int32)
    pools, last = core._paged_fns()[1](
        core.params, jnp.asarray(toks), core.pools, jnp.asarray(table[None]),
        jnp.zeros(1, jnp.int32), jnp.full((1,), 30, jnp.int32))
    out[f"logits{tp}"] = np.asarray(last, np.float32).ravel().tolist()
print(json.dumps(out))
"""


def test_tp4_build_shards_pools_and_matches_tp1():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"BENCH = {BENCH!r}\nTESTS = {os.path.dirname(__file__)!r}\n"
            + CHILD)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out.pop("mesh") == 4
    assert out.pop("logits1") == out.pop("logits4")
    assert all(out.values()), out


RUN_CHILD = """
import json, os, sys
sys.path[:0] = [BENCH, TESTS]
from conftest import DOCQA, make_checkout, run_cell
root = make_checkout(ROOT)
path = os.path.join(root, "bench", "traffic", "tiny-docqa.json")
with open(path) as f:
    mix = json.load(f)
mix["engine"]["tp"] = 4
with open(path, "w") as f:
    json.dump(mix, f)


def no_exchange(core):
    # each shard keeps its own partial O-proj and down-proj sums
    from repro.layers import attention, mlp
    local = lambda x, w, axis_name, **kw: x @ w
    attention.matmul_allreduce = mlp.matmul_allreduce = local


out = {}
for name, fault in (("sound", None), ("no_exchange", no_exchange)):
    rc, res = run_cell(root, DOCQA, break_engine=fault)
    out[name] = [rc, res["correct"], res["failed"], res["check"]]
print(json.dumps(out))
"""


def test_tp4_run_is_correct_and_not_without_the_exchange(tmp_path):
    """A whole run of the tiny document-QA cell on a tp=4 engine over
    four virtual devices is correct; with the AllReduces of the O-proj
    and down-proj partial sums left out underneath, it is not."""
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    code = (f"BENCH = {BENCH!r}\nTESTS = {os.path.dirname(__file__)!r}\n"
            f"ROOT = {str(tmp_path)!r}\n" + RUN_CHILD)
    p = subprocess.run([sys.executable, "-c", code], env=env,
                       capture_output=True, text=True, timeout=900)
    assert p.returncode == 0, p.stderr[-3000:]
    out = json.loads(p.stdout.strip().splitlines()[-1])
    assert out["sound"][:3] == [0, True, 0], out["sound"]
    assert out["no_exchange"][:2] == [0, False], out["no_exchange"]


def _old_serve_config(engine):
    """``serve_config`` as it was before a mix could ask for ``tp``."""
    from repro.config import ServeConfig
    return ServeConfig(
        max_batch=engine["max_batch"], max_seq_len=engine["max_seq_len"],
        page_size=engine["page_size"], num_pages=engine["num_pages"],
        prefill_chunk=engine["prefill_chunk"],
        prefix_cache=engine.get("prefix_cache", True),
        preempt_policy=engine.get("preempt_policy", "auto"),
        paged_impl=engine.get("paged_impl", "auto"))


@pytest.mark.parametrize("mix", ["offline-decode", "chat-sessions",
                                 "tp4-longdoc"])
def test_serve_config_takes_tp_from_the_mix(mix):
    import system
    with open(os.path.join(BENCH, "traffic", f"{mix}.json")) as f:
        engine = json.load(f)["engine"]
    got = system.serve_config(engine)
    assert got == dataclasses.replace(_old_serve_config(engine),
                                      tp=engine.get("tp", 1))
    assert (got.tp > 1) == ("tp" in engine)


def test_fill_pools_without_tp_is_unchanged():
    import system
    core = system.build(TINY, ENGINE, 3)
    assert core.tp_mesh is None
    mgr = core.mgr
    old = jax.jit(lambda: core.model.init_paged_cache(mgr.num_pages,
                                                      mgr.page_size))()
    assert jax.tree.structure(core.pools) == jax.tree.structure(old)
    for a, b in zip(jax.tree.leaves(core.pools), jax.tree.leaves(old)):
        assert (a.shape, a.dtype, a.sharding) == (b.shape, b.dtype,
                                                  b.sharding)
