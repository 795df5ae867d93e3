#!/usr/bin/env python3
"""The program's and the controls' readings over several seeds in one
process, judged by the benchmark's own comparison.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 40 \
        [--controls int8,e4m3]

For each seed it runs what a benchmark run runs (set-up with that seed's
weights, the window at the cell's own load, the drain), then checks the
same sample of finished greedy requests against the float32 reference:
the served tokens (the program), and at each position of the same
prompts and tokens the first choice of the reference computed with its
matrices in each control's precision (``reference.QUANT``).  Each side
goes through ``run.compare`` with the limits of
``bench/checks/<cell>.json``, as a benchmark run does: a control has to
come out not correct.  Prints one JSON line per seed with each side's
``correct`` and compared numbers, and what each control's quantisation
changed in the weights (``Reference.roundtrip_stats``).  The
benchmark's own runs never run this.
"""
from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

BENCH = os.path.dirname(os.path.abspath(__file__))
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

import run  # noqa: E402
from manifest import Manifest  # noqa: E402


def judge(numbers: dict, limits: dict) -> dict:
    compared, correct = run.compare(numbers, limits)
    return {"correct": correct, "check": compared}


def main(argv=None, *, root: str = run.ROOT, require_chip: bool = True,
         use_cache: bool = True, break_engine=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--controls", default="int8")
    args = ap.parse_args(argv)
    controls = args.controls.split(",")
    man = Manifest(root)
    cell = man.cell(args.workload)
    cfg, mix = man.config(cell), man.traffic(cell)
    with open(os.path.join(man.bench, "checks", f"{cell['name']}.json")) as f:
        limits = json.load(f)
    if use_cache:
        os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(root,
                                                               ".jax_cache")
    import jax
    if use_cache:
        jax.config.update("jax_compilation_cache_dir",
                          os.environ["JAX_COMPILATION_CACHE_DIR"])
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    if require_chip and jax.devices()[0].platform != "tpu":
        print("control: needs a TPU", file=sys.stderr)
        return 2
    from reference import Reference
    gen = man.generator(mix["kind"])
    max_len = mix["engine"]["max_seq_len"]
    for seed in (int(s) for s in args.seeds.split(",")):
        t0 = time.perf_counter()
        core, schedule = run.prepare(cfg, mix, seed, gen, [], break_engine)
        out = run.measure(core, schedule, mix, args.seconds, None,
                          time.perf_counter)
        del core
        gc.collect()
        t1 = time.perf_counter()
        line = {"seed": seed}
        for i, kind in enumerate(controls):
            nums = run.check(cfg, seed, max_len, out["recs"],
                             limits["sample"], control=kind)
            if i == 0:
                line["readings"] = {k: v for k, v in nums.items()
                                    if k != "control"}
                line["program"] = judge(nums, limits)
            line[f"control.{kind}"] = dict(
                judge(run.control_numbers(nums), limits),
                readings=nums["control"],
                roundtrip=Reference(cfg, seed, max_len,
                                    control=kind).roundtrip_stats())
        line.update(run_s=round(t1 - t0, 3),
                    check_s=round(time.perf_counter() - t1, 3))
        print(json.dumps(line), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
