"""Plain reference of a decoder LM with CAMformer attention, in float32
jax.numpy at "highest" matmul precision, with no kernel, cache or batching
of the program's.  It imports nothing of the program and takes nothing it
made: the weights are made again from the seed, layer by layer, by the
weights module the configuration names (``weights/<name>.py``), which the
harness hands it, so the program's weights and the reference's come from
one maker.

The model, per layer (RMSNorm eps 1e-6, RoPE on the two halves of each
head, gated SiLU feed-forward, optional q/k/v biases):

    h = rms(x) * ln1;  q, k, v = h Wq + bq, h Wk + bk, h Wv + bv
    q, k = rope(q), rope(k)
    s[p, j] = sign(q_p) . sign(k_j)          (sign(0) = -1), j <= p
    top = two-stage top-k of s[p, :]: the best stage1_k of every group of
          group_size keys (groups aligned at position 0), then the best
          k_top of those candidates (group-major, best first); ties go to
          the earlier key or candidate
    w = softmax over top of s * mean|q_p| * kscale(p) / sqrt(head_dim)
    x = x + (sum_top w v_j) Wo;  x = x + (silu(h2 Wg) * h2 Wu) Wd

``kscale(p)`` is the running mean of mean|k_j| (over the head dimension)
for j < e(p).  A decode position ``p >= L`` (L the prompt length) uses
e(p) = p + 1; a prompt position uses the end of its prefill chunk,
e(p) = min(slice * (p // slice + 1), L): the serving configuration
prefills prompts in chunks of ``slice`` tokens and each chunk's queries
take the key scale of everything written up to the chunk's end.

A control computes the same model in the precision below the one the
configuration states (``CONTROLS``): ``"float8"`` rounds every matrix
product's operands to float8 e4m3 (weights scaled per output column,
activations per row), accumulated in float32, for a bf16 configuration;
``"high"`` takes every product, the attention's weighted sum of values
too, at XLA's "high" precision (three bf16 passes on the TPU; the CPU
computes it in float32, so this control reads only on the chip), for a
float32 configuration at "highest".  It is not written out as bf16 parts:
XLA on the TPU folds a product of bf16-rounded float32 operands into one
bf16 pass.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

EPS = 1e-6
NEG = -1.0e9
LOGIT_ROWS = 512  # logits are made and reduced this many rows at a time
_F8 = jnp.float8_e4m3fn
_F8_MAX = 448.0


def _q8(x, axis):
    """Round to float8 e4m3 with a scale per slice along ``axis``."""
    amax = jnp.max(jnp.abs(x), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / _F8_MAX, 1.0)
    return (x / scale).astype(_F8).astype(jnp.float32) * scale


CONTROLS = ("float8", "high")


def _dot(spec, a, b, control):
    """einsum(spec, a, b) in float32 at "highest", or as ``control``."""
    prec = (jax.lax.Precision.HIGH if control == "high"
            else jax.lax.Precision.HIGHEST)
    return jnp.einsum(spec, a, b, precision=prec)


def _mm(x, w, control):
    if control == "float8":
        x, w = _q8(x, -1), _q8(w, 0)
    return _dot("...i,ij->...j", x, w, control)


def _rms(x, scale):
    return x * jax.lax.rsqrt(jnp.mean(x * x, -1, keepdims=True) + EPS) * scale


def _rope(x, pos, theta):
    """x (T, H, D), pos (T,)."""
    half = x.shape[-1] // 2
    freqs = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos[:, None, None].astype(jnp.float32) * freqs
    c, s = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * c - x2 * s, x2 * c + x1 * s], -1)


def _two_stage(s, k_top, group, stage1_k):
    """s (T, N) masked scores -> (values, indices) (T, k_top)."""
    t, n = s.shape
    v1, i1 = jax.lax.top_k(s.reshape(t, n // group, group), stage1_k)
    i1 = i1 + (jnp.arange(n // group) * group)[:, None]
    cand_v = v1.reshape(t, -1)
    cand_i = i1.reshape(t, -1)
    v2, sel = jax.lax.top_k(cand_v, min(k_top, cand_v.shape[-1]))
    return v2, jnp.take_along_axis(cand_i, sel, -1)


def _cam_head(qh, kh, vh, qscale, kscale, m, control):
    """One head of one sequence.  qh, kh, vh (T, D); qscale, kscale (T,)."""
    t, d = qh.shape
    qb = jnp.where(qh > 0, 1.0, -1.0).astype(jnp.bfloat16)
    kb = jnp.where(kh > 0, 1.0, -1.0).astype(jnp.bfloat16)
    s = jnp.matmul(qb, kb.T, preferred_element_type=jnp.float32)  # exact
    causal = jnp.arange(t)[None, :] <= jnp.arange(t)[:, None]
    s = jnp.where(causal, s, NEG)
    top_v, top_i = _two_stage(s, m["k_top"], m["group_size"], m["stage1_k"])
    valid = top_v > NEG / 2
    temp = (qscale * kscale)[:, None] / np.sqrt(d)
    w = jax.nn.softmax(jnp.where(valid, top_v * temp, NEG), -1)
    w = jnp.where(valid, w, 0.0)
    return _dot("tk,tkd->td", w, vh[top_i], control)


def _layer(w, x, pos, e_idx, m, control):
    """One layer over a batch of padded sequences.  x (N, T, d)."""
    n, t, _ = x.shape
    h, hkv, d = m["n_heads"], m["n_kv_heads"], m["head_dim"]
    a = _rms(x, w["ln1"])
    q, k, v = (_mm(a, w["wq"], control), _mm(a, w["wk"], control),
               _mm(a, w["wv"], control))
    if "bq" in w:
        q, k, v = q + w["bq"], k + w["bk"], v + w["bv"]
    theta = m["rope_theta"]
    q = jax.vmap(lambda z: _rope(z, pos, theta))(q.reshape(n, t, h, d))
    k = jax.vmap(lambda z: _rope(z, pos, theta))(k.reshape(n, t, hkv, d))
    v = v.reshape(n, t, hkv, d)
    qscale = jnp.mean(jnp.abs(q), -1)  # (N, T, H)
    kabs = jnp.mean(jnp.abs(k), -1)  # (N, T, Hkv)
    csum = jnp.cumsum(kabs, 1)
    kscale = (jnp.take_along_axis(csum, (e_idx - 1)[:, :, None], 1)
              / e_idx[:, :, None].astype(jnp.float32))
    g = h // hkv

    def one(args):
        qh, kh, vh, qs, ks = args
        return _cam_head(qh, kh, vh, qs, ks, m, control)

    # (N*H) heads, one at a time: the (T, T) scores of one head at once
    qs = q.transpose(0, 2, 1, 3).reshape(n * h, t, d)
    kv_of = (jnp.arange(n * h) // h) * hkv + (jnp.arange(n * h) % h) // g
    ks = k.transpose(0, 2, 1, 3).reshape(n * hkv, t, d)[kv_of]
    vs = v.transpose(0, 2, 1, 3).reshape(n * hkv, t, d)[kv_of]
    qsc = qscale.transpose(0, 2, 1).reshape(n * h, t)
    ksc = kscale.transpose(0, 2, 1).reshape(n * hkv, t)[kv_of]
    out = jax.lax.map(one, (qs, ks, vs, qsc, ksc))  # (N*H, T, D)
    out = out.reshape(n, h, t, d).transpose(0, 2, 1, 3).reshape(n, t, h * d)
    x = x + _mm(out, w["wo"], control)
    b = _rms(x, w["ln2"])
    ff = jax.nn.silu(_mm(b, w["w_gate"], control)) * _mm(b, w["w_up"],
                                                         control)
    return x + _mm(ff, w["w_down"], control)


def chunk_ends(prompt_len: int, total: int, t_pad: int, slice_: int):
    """e(p) for every padded position (see the module docstring)."""
    p = np.arange(t_pad)
    prompt_end = np.minimum(slice_ * (p // slice_ + 1), prompt_len)
    e = np.where(p < prompt_len, prompt_end, p + 1)
    return np.minimum(e, max(total, 1)).astype(np.int32)


class Reference:
    """Readings over sequences of (prompt, served tokens) for one model.

    ``weights`` is the configuration's weights module: the reference makes
    each layer's leaves again with its ``layer`` and the embedding and
    head with its ``top``.  ``t_pad`` and ``n_seq`` fix the shapes, so
    each jitted piece compiles once per configuration."""

    def __init__(self, model: dict, seed: int, *, t_pad: int, n_seq: int,
                 prefill_slice: int, weights, control: str | None = None):
        if control is not None and control not in CONTROLS:
            raise ValueError(f"control {control!r} is not one of {CONTROLS}")
        self.control = control
        self.m = model
        self.key = weights.root_key(seed)
        self.t_pad, self.n_seq, self.slice = t_pad, n_seq, prefill_slice
        m = model
        self._layer_w = jax.jit(
            lambda k, l: weights.layer(k, m, l, jnp.float32))
        self._top_w = jax.jit(lambda k: weights.top(k, m, jnp.float32))
        kinds = (None, control)
        self._layer = {c: jax.jit(functools.partial(_layer, m=m, control=c))
                       for c in kinds}
        self._final = {c: jax.jit(functools.partial(self._rows, control=c))
                       for c in kinds}

    def _rows(self, x, ln_f, head, rows, toks, ctrl_toks, *, control):
        """Logits at the (n, 2) (sequence, position) ``rows``; returns the
        gap of ``toks`` and of ``ctrl_toks`` below the best logit, and the
        argmax."""
        hid = _rms(x[rows[:, 0], rows[:, 1]], ln_f)
        logits = _mm(hid, head, control)[:, :self.m["vocab"]]
        best = logits.max(-1)
        pick = lambda t: best - jnp.take_along_axis(logits, t[:, None],
                                                    1)[:, 0]
        return pick(toks), pick(ctrl_toks), jnp.argmax(logits, -1)

    def _forward(self, seqs, control):
        m, t_pad = self.m, self.t_pad
        n = self.n_seq
        toks = np.zeros((n, t_pad), np.int32)
        e_idx = np.ones((n, t_pad), np.int32)
        for i, (prompt, served) in enumerate(seqs):
            full = list(prompt) + list(served)
            toks[i, :len(full)] = full
            e_idx[i] = chunk_ends(len(prompt), len(full), t_pad, self.slice)
        top = self._top_w(self.key)
        x = top["tok"][jnp.asarray(toks)]
        pos = jnp.arange(t_pad, dtype=jnp.int32)
        e_idx = jnp.asarray(e_idx)
        for l in range(m["n_layers"]):
            w = self._layer_w(self.key, jnp.uint32(l))
            x = self._layer[control](w, x, pos, e_idx)
            del w
        return x, top

    def readings(self, seqs, *, control: bool = False):
        """seqs: up to ``n_seq`` (prompt, served) pairs.  Returns one dict
        per sequence: ``gap`` (float32 array, per served token: how far
        the reference's logit of the served token lies below its best)
        and, with ``control``, ``control_gap`` (the same for the token the
        control, in the precision it was built with, puts first at each of
        those positions)."""
        if control and self.control is None:
            raise ValueError("this Reference was built without a control")
        if len(seqs) > self.n_seq:
            raise ValueError(f"{len(seqs)} sequences > n_seq {self.n_seq}")
        rows, served = [], []
        for i, (prompt, out) in enumerate(seqs):
            for j, tok in enumerate(out):
                rows.append((i, len(prompt) - 1 + j))
                served.append(tok)
        rows = np.asarray(rows, np.int32).reshape(-1, 2)
        served = np.asarray(served, np.int32)
        n_rows = len(served)
        pad = -n_rows % LOGIT_ROWS
        rows = np.concatenate([rows, np.zeros((pad, 2), np.int32)])
        served = np.concatenate([served, np.zeros(pad, np.int32)])

        def sweep(ctrl, ctrl_toks):
            x, top = self._forward(seqs, ctrl)
            gaps, cgaps, arg = [], [], []
            for s in range(0, len(served), LOGIT_ROWS):
                sl = slice(s, s + LOGIT_ROWS)
                g, cg, a = self._final[ctrl](
                    x, top["ln_f"], top["head"], jnp.asarray(rows[sl]),
                    jnp.asarray(served[sl]), jnp.asarray(ctrl_toks[sl]))
                gaps.append(np.asarray(g))
                cgaps.append(np.asarray(cg))
                arg.append(np.asarray(a))
            return (np.concatenate(gaps), np.concatenate(cgaps),
                    np.concatenate(arg))

        with jax.default_matmul_precision("highest"):
            ctrl_first = np.zeros_like(served)
            if control:
                _, _, ctrl_first = sweep(self.control, ctrl_first)
            gap, ctrl_gap, _ = sweep(None, ctrl_first)
        out, at = [], 0
        for prompt, tokens in seqs:
            k = len(tokens)
            d = {"gap": gap[at:at + k]}
            if control:
                d["control_gap"] = ctrl_gap[at:at + k]
            out.append(d)
            at += k
        return out
