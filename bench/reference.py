"""The plain reference: the configuration's model family in float32.

It follows the published architecture, not the program under test, and
imports nothing of it.  The layers are the family's (``block`` of
``bench/families/<reference>.py``, given each layer's index); before
them comes the token embedding, after them the family's ``head`` (by
default a final RMSNorm and the head matrix, the embedding where the
configuration ties them).  Weights come from ``weights.py``
for the same seed, rounded to bfloat16 as they are served, and every
product runs in float32 at the highest precision.  Layers are made and
applied one at a time and sequences one at a time, so the reference fits
beside nothing else on one chip.  ``rms``, ``rope`` and ``attention``
are the float32 pieces a family's layer is built from.

``control="int8"`` also computes the same forward with every matrix the
family lists in ``MATRICES`` and the head quantised to int8 per output
column (symmetric, one scale per column), the next precision below
bfloat16 that a program could be tempted to serve in; ``control="e4m3"``
does the same in float8 e4m3 (each column scaled to the format's largest
value, 448).  A matrix with a leading axis (one per expert) is
quantised per output column of each.
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

import weights as W
from manifest import family_of

HEAD_ROWS = 128


def int8_roundtrip(w: jax.Array) -> jax.Array:
    """Quantise a (..., in, out) float32 matrix to int8 with one scale per
    output column and back."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 127.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return jnp.clip(jnp.round(w / scale), -127, 127) * scale


def e4m3_round(x: jax.Array) -> jax.Array:
    """Round float32 values within +-448 to the nearest float8 e4m3 value
    (ties to even), by arithmetic: 3 mantissa bits down to the exponent
    -6 and steps of 2**-9 below it.  A cast through ``float8_e4m3fn``
    does not do this on a TPU v5e: there it changed 70% of the weights
    by 0.12%, not 99% by 2.6% (PERF.md)."""
    bits = jax.lax.bitcast_convert_type(x, jnp.int32)
    exp = ((bits >> 23) & 0xFF) - 127              # x's binary exponent
    step_exp = jnp.maximum(exp - 3, -9)
    step = jax.lax.bitcast_convert_type((step_exp + 127) << 23, jnp.float32)
    return jnp.round(x / step) * step


def e4m3_roundtrip(w: jax.Array) -> jax.Array:
    """Quantise a (..., in, out) float32 matrix to float8 e4m3 with one
    scale per output column (its largest magnitude to 448) and back."""
    scale = jnp.max(jnp.abs(w), axis=-2, keepdims=True) / 448.0
    scale = jnp.where(scale == 0, 1.0, scale)
    return e4m3_round(w / scale) * scale


QUANT = {"int8": int8_roundtrip, "e4m3": e4m3_roundtrip}


def rms(x, scale, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + eps) * scale


def rope(x, pos, theta):
    """x: (S, H, hd); rotate the first and second halves of each head."""
    hd = x.shape[-1]
    freqs = 1.0 / theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd)
    ang = pos[:, None].astype(jnp.float32) * freqs
    sin, cos = jnp.sin(ang)[:, None], jnp.cos(ang)[:, None]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def default_head(cfg, x, norm, w):
    """A family's ``head`` where it defines none: the final RMSNorm, then
    the (D, V) head matrix."""
    return rms(x, norm, cfg["rms_norm_eps"]) @ w


def attention(q, k, v):
    """Causal softmax attention of q: (S, Hq, hd) over k, v: (S, Hkv, hd),
    each KV head shared by ``Hq / Hkv`` query heads; returns the heads'
    outputs side by side, (S, Hq * hd)."""
    s, hq, hd = q.shape
    hkv = k.shape[1]
    pos = jnp.arange(s)
    q = q.reshape(s, hkv, hq // hkv, hd)
    out = []
    blk = 512 if s % 512 == 0 else s
    for q0 in range(0, s, blk):       # query blocks bound the score tensor
        qb = q[q0:q0 + blk]
        sc = jnp.einsum("qgrd,kgd->grqk", qb, k) / jnp.sqrt(float(hd))
        causal = (q0 + jnp.arange(qb.shape[0]))[:, None] >= pos[None, :]
        sc = jnp.where(causal, sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        out.append(jnp.einsum("grqk,kgd->qgrd", p, v))
    return jnp.concatenate(out, 0).reshape(s, hq * hd)


class Reference:
    """Float32 logits of the configuration for the weights of ``seed``;
    ``control`` (a key of ``QUANT``) adds the forward with the family's
    ``MATRICES`` in that precision beside it."""

    def __init__(self, cfg: dict, seed: int, max_len: int,
                 control: Optional[str] = None):
        if control is not None and control not in QUANT:
            raise ValueError(f"unknown control {control!r}")
        self.cfg, self.max_len, self.control = cfg, max_len, control
        self.key = W.base_key(seed)
        self.family = fam = family_of(cfg)
        cfg_ = cfg
        quantise = QUANT.get(control)

        def layer_f32(key, i, shapes, quant):
            w = {n: a.astype(jnp.float32)
                 for n, a in W.layer(cfg_, key, i, dict(shapes)).items()}
            if quant:
                w.update({n: quantise(w[n]) for n in fam.MATRICES
                          if n in w})
            return w

        # one program for each set of tensor shapes (each kind of layer)
        self._layer = jax.jit(layer_f32, static_argnums=(2, 3))

        def block(w, x, n, i):
            with jax.default_matmul_precision("highest"):
                return fam.block(cfg_, w, x, n, i)

        self._block = jax.jit(block)
        family_head = getattr(fam, "head", default_head)

        def head(hw, fn, x, start):
            # a fixed block of rows from ``start`` (one program for every
            # request; rows past the sequence are clipped and dropped)
            rows = jnp.take(x, start + jnp.arange(HEAD_ROWS), axis=0,
                            mode="clip")
            with jax.default_matmul_precision("highest"):
                return family_head(cfg_, rows, fn, hw)

        self._head = jax.jit(head)
        self._quant = jax.jit(quantise) if quantise else None

    def _layer_of(self, i: int, quant: bool) -> Dict[str, jax.Array]:
        shapes = self.family.layer_shapes(self.cfg, i)
        return self._layer(self.key, jnp.int32(i),
                           tuple((n, tuple(s)) for n, s in shapes.items()),
                           quant)

    def roundtrip_stats(self) -> Dict[str, float]:
        """What the control's quantisation does to the served weights, on
        the device through the control's own programs: the share of the
        elements of layer 0's matrices and of the head that it changes,
        and its error (Frobenius norm of the change over the matrix's)."""
        ws, qs = self._layer_of(0, False), self._layer_of(0, True)
        pairs = [(ws[n], qs[n]) for n in self.family.MATRICES if n in ws]
        hw = W.lm_head(self.cfg, self.key).astype(jnp.float32)
        pairs.append((hw, self._quant(hw)))
        changed = sum(float(jnp.sum(w != q)) for w, q in pairs)
        size = sum(w.size for w, _ in pairs)
        err = np.sqrt(sum(float(jnp.sum((w - q) ** 2)) for w, q in pairs)
                      / sum(float(jnp.sum(w * w)) for w, _ in pairs))
        return {"changed_share": changed / size, "rel_error": float(err)}

    def logits(self, seqs: Sequence[Tuple[Sequence[int], int, int]]
               ) -> List[Dict[str, np.ndarray]]:
        """For each ``(tokens, first, count)``: the float32 logits rows
        ``first .. first+count-1`` of ``tokens`` (row t predicts token
        t+1) under ``"ref"``; with ``control`` also the control forward's
        rows under its name."""
        cfg, L = self.cfg, self.cfg["num_hidden_layers"]
        emb = W.embed(cfg, self.key)
        streams = {"ref": []}
        if self.control:
            streams[self.control] = []
        for toks, _, _ in seqs:
            buf = np.zeros((self.max_len,), np.int32)
            buf[:len(toks)] = toks
            x = jnp.take(emb, jnp.asarray(buf), axis=0).astype(jnp.float32)
            for name in streams:
                streams[name].append(x)
        del emb
        for i in range(L):
            for name, xs in streams.items():
                w = self._layer_of(i, name != "ref")
                for j, (toks, _, _) in enumerate(seqs):
                    xs[j] = self._block(w, xs[j], len(toks), jnp.int32(i))
                del w
        hw = W.lm_head(self.cfg, self.key).astype(jnp.float32)
        fn = W.final_norm(self.cfg).astype(jnp.float32)
        out = [dict() for _ in seqs]
        for name, xs in streams.items():
            w = self._quant(hw) if name != "ref" else hw
            for j, (_, first, count) in enumerate(seqs):
                out[j][name] = np.concatenate([
                    np.asarray(self._head(w, fn, xs[j], jnp.int32(s)))
                    for s in range(first, first + count, HEAD_ROWS)
                ])[:count]
            del w
        return out


def gaps(ref_rows: np.ndarray, tokens: Sequence[int]) -> np.ndarray:
    """By how much each token's reference logit lies below the
    reference's best in its row."""
    picked = ref_rows[np.arange(len(tokens)), np.asarray(tokens)]
    return ref_rows.max(axis=-1) - picked
