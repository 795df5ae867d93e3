"""chip_smoke.py off the chip: its waves and checks at a tiny size on the
CPU (the jittable paged reference), its tp comparison on four fake CPU
devices, and its refusal to run without a TPU."""
import json
import os
import shutil
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402
from repro.config import (ParallelConfig, get_model_config,  # noqa: E402
                          reduce_for_smoke)
from repro.models import build_model  # noqa: E402

SMOKE = """
import json, sys
sys.path.insert(0, {root!r})
import dataclasses
import chip_smoke as cs
from repro.config import ParallelConfig, get_model_config, reduce_for_smoke
from repro.models import build_model
# 4 heads of 16, MHA like phi3: tp=4 is pure head parallelism (g=4, s=1)
cfg = dataclasses.replace(reduce_for_smoke(get_model_config(cs.ARCH)),
                          num_heads=4, num_kv_heads=4, d_model=64, d_ff=256)
model = build_model(cfg, ParallelConfig())
fails = cs.run_tp(model, cfg, 0, 4, cs.CompileClock(), ps=16,
                  paged_impl="paged_reference", want_impl="paged_reference")
print(json.dumps(fails))
"""


def _child_env(**kw):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=os.path.join(ROOT, "src"))
    env.update(kw)
    return env


def test_waves_drive_swap_cow_and_match_reference(capsys):
    """The one-chip run at page size 16: every request finishes, one
    sequence is swapped out and back, a full prefix hit copy-on-writes,
    and the engine's logits and greedy tokens match LM.apply."""
    cfg = reduce_for_smoke(get_model_config(chip_smoke.ARCH))
    model = build_model(cfg, ParallelConfig())
    fails = chip_smoke.run_one_chip(model, cfg, 0, chip_smoke.CompileClock(),
                                    ps=16, paged_impl="paged_reference",
                                    want_impl="paged_reference")
    out = capsys.readouterr().out
    assert fails == [], out
    assert "requests finished 8/8" in out
    assert "first-token top-1 agreement 8/8" in out


def test_tp4_engine_matches_tp1_on_fake_devices():
    """--tp 4 compares a 4-way engine (one kv head per device, the
    plan phi3-mini gets on four chips) with the tp=1 engine."""
    out = subprocess.run(
        [sys.executable, "-c", SMOKE.format(root=ROOT)],
        env=_child_env(
            XLA_FLAGS="--xla_force_host_platform_device_count=4"),
        capture_output=True, text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert json.loads(out.stdout.strip().splitlines()[-1]) == [], out.stdout
    assert "identical greedy streams 7/7" in out.stdout


@pytest.mark.parametrize("alone", [False, True],
                         ids=["no_tpu", "outside_checkout"])
def test_refuses_without_tpu_or_repo(tmp_path, alone):
    """No TPU, or no repo beside the script: non-zero exit, no result."""
    script = os.path.join(ROOT, "chip_smoke.py")
    if alone:
        script = shutil.copy(script, tmp_path)
    out = subprocess.run([sys.executable, script], cwd=tmp_path,
                         env=_child_env(PYTHONPATH=""),
                         capture_output=True, text=True, timeout=300)
    assert out.returncode != 0
    assert '"ok"' not in out.stdout
    if not alone:
        assert out.returncode == 2 and "no TPU" in out.stderr
