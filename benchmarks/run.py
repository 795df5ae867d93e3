"""Benchmark harness: one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows.  This container is CPU-only,
so wall-times are CPU-scaled (shapes reduced, same algorithmic structure);
`derived` carries the paper-comparable quantity (speedup ratio, memory
saving, collective count, max context) which is shape- and
hardware-portable.  See EXPERIMENTS.md for the TPU-target roofline view.

    PYTHONPATH=src python -m benchmarks.run [--only fig7,table3]
"""
from __future__ import annotations

import argparse
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _timeit(fn, *args, n=5, warmup=2):
    for _ in range(warmup):
        jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(n):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / n * 1e6     # us


def row(name, us, derived):
    print(f"{name},{us:.1f},{derived}", flush=True)


# ---------------------------------------------------------------------------
# Figure 7: FastAttention operator vs standard attention (PanGu dims)
# ---------------------------------------------------------------------------

def bench_fig7_operator_speedup():
    from repro.kernels.fastattn.ref import flash_reference, \
        standard_attention
    rng = np.random.default_rng(0)
    # paper Sec 5.2.1: B=1, N=5 heads (PanGu-38B TP slice), D=128
    for name, heads in (("pangu38b", 5), ("pangu71b", 4)):
        for s in (1024, 2048, 4096):
            q = jnp.asarray(rng.normal(size=(1, heads, s, 128)),
                            jnp.float32)
            k, v = q + 0.1, q - 0.1
            std = jax.jit(lambda q, k, v: standard_attention(
                q, k, v, causal=True))
            fast = jax.jit(lambda q, k, v: flash_reference(
                q, k, v, causal=True, block_kv=1024))
            t_std = _timeit(std, q, k, v, n=3)
            t_fast = _timeit(fast, q, k, v, n=3)
            row(f"fig7_{name}_s{s}_standard", t_std, "")
            row(f"fig7_{name}_s{s}_fastattn", t_fast,
                f"speedup={t_std / t_fast:.2f}x")


# ---------------------------------------------------------------------------
# Figure 8: TFLOPs/s across sequence lengths, +-causal
# ---------------------------------------------------------------------------

def bench_fig8_tflops():
    from repro.kernels.fastattn.ref import flash_reference
    rng = np.random.default_rng(1)
    b, h, d = 2, 8, 32          # CPU-scaled from paper's B=8 H=64
    for causal in (False, True):
        for s in (1024, 2048, 4096):
            q = jnp.asarray(rng.normal(size=(b, h, s, d)), jnp.float32)
            fast = jax.jit(lambda q: flash_reference(
                q, q, q, causal=causal, block_kv=512))
            us = _timeit(fast, q, n=3)
            flops = 4 * s * s * d * h * b * (0.5 if causal else 1.0)
            row(f"fig8_s{s}_causal{int(causal)}", us,
                f"gflops_per_s={flops / us / 1e3:.2f}")


# ---------------------------------------------------------------------------
# Figure 9: two-level tiling block-size sweep (latency vs level-1 size)
# ---------------------------------------------------------------------------

def bench_fig9_blocksize():
    from repro.kernels.fastattn.ref import flash_reference
    from repro.core.tiling import plan_two_level_tiling, sync_count
    rng = np.random.default_rng(2)
    s, h, d = 4096, 5, 128
    q = jnp.asarray(rng.normal(size=(1, h, s, d)), jnp.float32)
    base_us = None
    for bs in (128, 256, 512, 1024, 2048):
        fast = jax.jit(lambda q: flash_reference(q, q, q, causal=True,
                                                 block_kv=bs))
        us = _timeit(fast, q, n=3)
        if base_us is None:
            base_us = us
        red = 100 * (1 - us / base_us)
        row(f"fig9_bs{bs}", us,
            f"latency_reduction_vs_bs128={red:.1f}%;"
            f"syncs={sync_count(s, bs)}")
    plan = plan_two_level_tiling(s, s, d)
    row("fig9_planner_choice", 0,
        f"block_q={plan.block_q};block_kv1={plan.block_kv1};"
        f"block_kv2={plan.block_kv2};vmem_bytes={plan.vmem_bytes}")


# ---------------------------------------------------------------------------
# Table 2: ablation of the proposed strategies
# ---------------------------------------------------------------------------

def bench_table2_ablation():
    from repro.kernels.fastattn.ref import flash_reference, \
        standard_attention
    from repro.core.tiling import plan_two_level_tiling
    rng = np.random.default_rng(3)
    s, h, d = 2048, 5, 128
    q = jnp.asarray(rng.normal(size=(1, h, s, d)), jnp.float32)
    t_std = _timeit(jax.jit(lambda q: standard_attention(q, q, q,
                                                         causal=True)),
                    q, n=3)
    t_unified = _timeit(jax.jit(lambda q: flash_reference(
        q, q, q, causal=True, block_kv=128)), q, n=3)
    plan = plan_two_level_tiling(s, s, d)
    t_two = _timeit(jax.jit(lambda q: flash_reference(
        q, q, q, causal=True, block_kv=plan.block_kv1)), q, n=3)
    row("table2_standard", t_std, "speedup=1.00x")
    row("table2_unified_tiling", t_unified,
        f"speedup={t_std / t_unified:.2f}x")
    row("table2_two_level_tiling", t_two,
        f"speedup={t_std / t_two:.2f}x")
    # tiling-mask: memory + skipped-block accounting (arch-agnostic)
    from repro.core import tiling_mask as tm
    spec = tm.MaskSpec(causal=True)
    first, last = spec.block_limits(s // 128, s // 128, 128, 128, s)
    visited = int(np.sum(last - first + 1))
    total = (s // 128) ** 2
    row("table2_tiling_mask", 0,
        f"mask_mem={tm.m_mask_memory_bytes(256)}B_vs_"
        f"{tm.mask_memory_bytes(s)}B;cube_blocks_skipped="
        f"{100 * (1 - visited / total):.0f}%")


# ---------------------------------------------------------------------------
# Figures 10/16/17: tiling-AllReduce (T3) on an 8-device mesh
# ---------------------------------------------------------------------------

def bench_tiling_allreduce():
    code = r"""
import json, time, functools
import jax, jax.numpy as jnp, numpy as np
from jax.sharding import PartitionSpec as P
from repro.core.tiled_allreduce import make_sharded_fused_block
from repro.analysis.hlo import analyze_hlo_text
from repro.launch.mesh import make_mesh
mesh = make_mesh((8,), ('model',))
rng = np.random.default_rng(0)
b, s, h, d, dm = 1, 512, 40, 16, 640      # 40 heads / 8 = 5 per device
q = jnp.asarray(rng.normal(size=(b, s, h, d)), jnp.float32)
wo = jnp.asarray(rng.normal(size=(h*d, dm)) * 0.05, jnp.float32)
out = {}
for mode, chunks in (('single', 1), ('tiled', 4), ('tiled8', 8)):
    f = make_sharded_fused_block(mesh, mode='tiled' if 'tiled' in mode
                                 else 'single',
                                 n_chunks=chunks, causal=True)
    jf = jax.jit(f)
    r = jf(q, q, q, wo); jax.block_until_ready(r)
    t0 = time.perf_counter()
    for _ in range(3):
        r = jf(q, q, q, wo)
    jax.block_until_ready(r)
    us = (time.perf_counter() - t0) / 3 * 1e6
    cost = analyze_hlo_text(jf.lower(q, q, q, wo).compile().as_text())
    n_ar = sum(n for _, _, n in cost.top_collectives)
    out[mode] = dict(us=us, n_allreduce=n_ar,
                     coll_bytes=cost.collective_bytes)
f1 = jax.jit(make_sharded_fused_block(mesh, mode='single', causal=True))
f2 = jax.jit(make_sharded_fused_block(mesh, mode='tiled', n_chunks=4,
                                      causal=True))
err = float(jnp.max(jnp.abs(f1(q, q, q, wo) - f2(q, q, q, wo))))
out['max_err'] = err
print(json.dumps(out))
"""
    # the child's devices are fake CPU ones: on a TPU host it must not
    # try to take the chip this process already holds
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=8",
               PYTHONPATH=os.path.join(ROOT, "src"))
    res = subprocess.run([sys.executable, "-c", code], env=env,
                         capture_output=True, text=True, timeout=600)
    if res.returncode != 0:
        row("fig10_tiling_allreduce", 0, f"ERROR:{res.stderr[-200:]}")
        return
    out = json.loads(res.stdout.strip().splitlines()[-1])
    for mode in ("single", "tiled", "tiled8"):
        r = out[mode]
        row(f"fig10_allreduce_{mode}", r["us"],
            f"n_allreduce={r['n_allreduce']};"
            f"coll_bytes={int(r['coll_bytes'])};"
            f"overlappable={'no' if mode == 'single' else 'yes'}")
    row("fig10_allreduce_equivalence", 0, f"max_err={out['max_err']:.2e}")


# ---------------------------------------------------------------------------
# Table 3: CPU-GPU cooperative strategy vs classical offloading
# ---------------------------------------------------------------------------

def bench_table3_offload():
    from repro.config import get_model_config
    from repro.core.offload import (max_context_length, table3_row)
    cfg = get_model_config("pangu-38b")
    for s in (1024, 16384, 65536, 262144):
        r = table3_row(cfg, s, device_memory_gb=16)
        if not r["offload"]:
            row(f"table3_s{s}", r["gpu_calc_s"] * 1e6, "offload=no")
        else:
            row(f"table3_s{s}_classical", r["classical_total_s"] * 1e6,
                f"upload_ms={r['classical_upload_s'] * 1e3:.2f}")
            row(f"table3_s{s}_cooperative", r["coop_total_s"] * 1e6,
                f"speedup={r['speedup']:.2f}x;l_cpu={r['l_cpu']};"
                f"l_gpu={r['l_gpu']}")
    mc = max_context_length(cfg, batch=1, n_devices=8, device_memory_gb=16,
                            host_memory_gb=768)
    row("table3_max_context", 0,
        f"device_only={mc['device_only']};"
        f"cooperative={mc['cooperative']};"
        f"extension={mc['cooperative'] / max(mc['device_only'], 1):.1f}x")
    # measured host-attention data path (engine smoke, CPU-real)
    from repro.core.offload import HostOffloadEngine, OffloadPlan
    cfg_s = get_model_config("whisper-small")
    plan = OffloadPlan(1, 1, 0, 0, 0, 0, 0, True)
    eng = HostOffloadEngine(cfg_s, plan, max_batch=1, max_seq=2048)
    rng = np.random.default_rng(0)
    k = jnp.asarray(rng.normal(size=(1, 2048, cfg_s.num_kv_heads,
                                     cfg_s.head_dim)), jnp.float32)
    eng.prefill_offload(0, k, k)
    q = jnp.asarray(rng.normal(size=(1, 1, cfg_s.num_heads,
                                     cfg_s.head_dim)), jnp.float32)
    us = _timeit(lambda: eng.decode_attention(0, q, [2048]), n=3)
    row("table3_host_attention_measured", us, "kv_len=2048")


# ---------------------------------------------------------------------------
# Tables 4/5/6: end-to-end latency & throughput (reduced models)
# ---------------------------------------------------------------------------

def bench_e2e_throughput():
    from repro.config import (ParallelConfig, ServeConfig,
                              get_model_config, reduce_for_smoke)
    from repro.models import build_model
    from repro.serving.engine import ServeEngine
    cfg = reduce_for_smoke(get_model_config("llama2-7b"))
    model = build_model(cfg, ParallelConfig(remat="none"))
    params = model.init(jax.random.PRNGKey(0))
    for batch in (1, 4, 8):
        eng = ServeEngine(model=model, params=params, cfg=cfg,
                          serve=ServeConfig(max_seq_len=128))
        tps = eng.throughput_tokens_per_s(batch, 32, n_new=8)
        row(f"table6_llama2-7b_b{batch}", 1e6 / max(tps, 1e-9),
            f"tokens_per_s={tps:.1f}")


# ---------------------------------------------------------------------------
# Mask memory table (paper Sec 4.1 numbers, exact)
# ---------------------------------------------------------------------------

def bench_mask_memory():
    from repro.core import tiling_mask as tm
    for s in (16384, 65536, 262144):
        dense = tm.mask_memory_bytes(s, 2)
        mmask = tm.m_mask_memory_bytes(512, 1)
        row(f"maskmem_s{s}", 0,
            f"dense={dense / 2**30:.2f}GiB;mmask={mmask / 2**10:.0f}KiB;"
            f"saving={dense / mmask:.0f}x")


BENCHES = {
    "fig7": bench_fig7_operator_speedup,
    "fig8": bench_fig8_tflops,
    "fig9": bench_fig9_blocksize,
    "table2": bench_table2_ablation,
    "fig10": bench_tiling_allreduce,
    "table3": bench_table3_offload,
    "table6": bench_e2e_throughput,
    "maskmem": bench_mask_memory,
}


def main(argv=None):
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None,
                    help="comma-separated subset of: " + ",".join(BENCHES))
    args = ap.parse_args(argv)
    names = args.only.split(",") if args.only else list(BENCHES)
    print("name,us_per_call,derived")
    for name in names:
        BENCHES[name]()


if __name__ == "__main__":
    main()
