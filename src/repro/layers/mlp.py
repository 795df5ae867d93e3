"""Dense MLP blocks: SwiGLU / GeGLU / GELU, column->row parallel on `ff`."""
from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.core.tiled_allreduce import matmul_allreduce
from repro.layers import common
from repro.sharding.rules import constrain
from repro.sharding.tp import current_tp


def init_mlp(key, d: int, f: int, mlp_type: str, dtype):
    ks = jax.random.split(key, 3)
    p = {"w_up": common.dense_init(ks[0], d, f, dtype),
         "w_down": common.dense_init(ks[1], f, d, dtype)}
    if mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = common.dense_init(ks[2], d, f, dtype)
    return p


def mlp_logical(d: int, f: int, mlp_type: str):
    p = {"w_up": (("d_model", "ff"), (d, f)),
         "w_down": (("ff", "d_model"), (f, d))}
    if mlp_type in ("swiglu", "geglu"):
        p["w_gate"] = (("d_model", "ff"), (d, f))
    return p


def apply_mlp(params, x, mlp_type: str = "swiglu"):
    tpc = current_tp()
    if tpc is not None and params["w_up"].shape[1] % tpc.plan.tp == 0:
        return _tp_apply_mlp(params, x, mlp_type, tpc)
    h = common.dense(x, params["w_up"])
    if mlp_type == "swiglu":
        h = jax.nn.silu(common.dense(x, params["w_gate"])) * h
    elif mlp_type == "geglu":
        h = jax.nn.gelu(common.dense(x, params["w_gate"])) * h
    else:
        h = jax.nn.gelu(h)
    h = constrain(h, "batch", "seq", "ff")
    return common.dense(h, params["w_down"])


def _tp_apply_mlp(params, x, mlp_type: str, tpc):
    """Megatron column->row parallel MLP over the paged-TP mesh.

    Inputs enter replicated; each shard takes a 1/tp column slice of
    w_up/w_gate (indexed by its linear mesh position), applies the
    activation on its ff slice, and multiplies against the matching
    w_down row slice, partial-summed over both mesh axes with the
    tiling-AllReduce.  Falls back to the replicated path (caller) when
    d_ff does not divide tp.
    """
    plan, mesh = tpc.plan, tpc.mesh
    heads_ax, seq_ax = plan.axes
    tp, fl = plan.tp, params["w_up"].shape[1] // plan.tp

    def body(prm, xb):
        li = jax.lax.axis_index(heads_ax) * plan.s + \
            jax.lax.axis_index(seq_ax)
        f0 = li * fl
        w_up = jax.lax.dynamic_slice_in_dim(prm["w_up"], f0, fl, axis=1)
        h = common.dense(xb, w_up)
        if mlp_type == "swiglu":
            w_gate = jax.lax.dynamic_slice_in_dim(prm["w_gate"], f0, fl, 1)
            h = jax.nn.silu(common.dense(xb, w_gate)) * h
        elif mlp_type == "geglu":
            w_gate = jax.lax.dynamic_slice_in_dim(prm["w_gate"], f0, fl, 1)
            h = jax.nn.gelu(common.dense(xb, w_gate)) * h
        else:
            h = jax.nn.gelu(h)
        w_down = jax.lax.dynamic_slice_in_dim(prm["w_down"], f0, fl, axis=0)
        b, s, _ = xb.shape
        y = matmul_allreduce(h.reshape(b * s, fl), w_down, plan.axes,
                             mode=plan.collectives, n_chunks=plan.ar_chunks,
                             first_chunk_frac=plan.first_chunk_frac)
        return y.reshape(b, s, -1)

    return jax.shard_map(body, mesh=mesh, in_specs=(P(), P()),
                         out_specs=P(), check_vma=False)(params, x)
