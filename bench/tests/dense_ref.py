"""Plain reference of a stand-in second detector, for the CPU tests.

The program's ``dense`` family as an embeds-in detector over frame patches,
written from its description in plain ``jax.numpy``:

- each ``H x W`` frame is cut into ``patch x patch`` patches, projected to
  ``d_model`` and given a learned position embedding, as in
  ``bench/reference/hubert.py``;
- pre-norm blocks: RMS norm, causal grouped-query attention (``kv_heads``
  key and value heads, each shared by ``n_heads / kv_heads`` query heads)
  with rotary position embedding on queries and keys, residual; RMS norm,
  gated SiLU feed-forward, residual;
- a final RMS norm and the output projection; the detector reads the first
  ``n_out`` outputs at the last position.

Every product runs on operands in the mode's compute dtype with float32
accumulation; norm statistics, softmax and the rotary arithmetic stay in
float32. It keeps the detector references' contract
(``bench/reference/__init__.py``), so a test can add this detector to the
benchmark with files and entries alone.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn

#: the configuration's bfloat16, float32, and the float8 control
MODES = {"bfloat16": (jnp.bfloat16, False), "float32": (jnp.float32, False),
         "float8": (jnp.bfloat16, True)}


def tokens(g: dict, d: dict) -> int:
    return (g["frame_h"] // d["patch"]) * (g["frame_w"] // d["patch"])


@functools.partial(jax.jit, static_argnames=("shapes",))
def _init(key, *, shapes):
    """Weights from ``key``: fan-in scaled normals, norm scales near 1."""
    L, dm, nh, kv, hd, f, vocab, pp, seq = shapes
    ks = jax.random.split(key, 13)

    def nrm(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    def scale(k, shape):
        return 1.0 + 0.1 * jax.random.normal(k, shape, jnp.float32)

    return {
        "backbone": {
            "final_norm": {"scale": scale(ks[0], (dm,))},
            "unembed": {"kernel": nrm(ks[1], (dm, vocab), dm)},
            "layers": {
                "attn_norm": {"scale": scale(ks[2], (L, dm))},
                "attn": {"wq": nrm(ks[3], (L, dm, nh, hd), dm),
                         "wk": nrm(ks[4], (L, dm, kv, hd), dm),
                         "wv": nrm(ks[5], (L, dm, kv, hd), dm),
                         "wo": nrm(ks[6], (L, nh, hd, dm), nh * hd)},
                "mlp_norm": {"scale": scale(ks[7], (L, dm))},
                "mlp": {"w_gate": nrm(ks[8], (L, dm, f), dm),
                        "w_up": nrm(ks[9], (L, dm, f), dm),
                        "w_down": nrm(ks[10], (L, f, dm), f)},
            },
        },
        "embedder": {
            "proj": jax.random.normal(ks[11], (pp, dm), jnp.float32)
            / float(np.sqrt(pp)),
            "pos": 0.02 * jax.random.normal(ks[12], (seq, dm), jnp.float32),
        },
    }


def make_weights(key, g: dict, d: dict) -> dict:
    shapes = (d["n_layers"], d["d_model"], d["n_heads"], d["kv_heads"],
              d["d_model"] // d["n_heads"], d["d_ff"], d["vocab"],
              d["patch"] * d["patch"], tokens(g, d))
    return _init(key, shapes=shapes)


def frame_flops(g: dict, d: dict) -> float:
    """Forward FLOPs of the decoder layers for one real frame.

    Per token and layer: the query and output projections (4 d h hd), the
    key and value projections (4 d kv hd), the gated feed-forward's three
    products (6 d d_ff); per layer, the causal scores and weighted sum,
    in which query ``i`` meets ``i + 1`` keys (2 h hd s (s + 1)).
    """
    s = tokens(g, d)
    dm, f, nh, kv = d["d_model"], d["d_ff"], d["n_heads"], d["kv_heads"]
    hd = dm // nh
    per_token = 4 * dm * hd * (nh + kv) + 6 * dm * f
    return float(d["n_layers"] * (s * per_token + 2 * nh * hd * s * (s + 1)))


def tiny(d: dict) -> dict:
    """The keys of ``d`` a CPU test shrinks: two layers of width 64, two
    query heads to each key head."""
    return {"n_layers": 2, "d_model": 64, "n_heads": 4, "kv_heads": 2,
            "d_ff": 128, "vocab": 64, "batch": 2}


def _rms_norm(x, scale, cd):
    var = jnp.mean(jnp.square(x.astype(jnp.float32)), axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + 1e-6).astype(cd) * scale.astype(cd)


def _rope(x, theta):
    """Rotary embedding on ``(s, heads, hd)``, halves not interleaved."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _one_frame(params, frame, *, patch, n_out, theta, cd, q8):
    H, W = frame.shape
    seq = (H // patch) * (W // patch)

    def mm(spec, a, b):
        a, b = a.astype(cd), b.astype(cd)
        if q8:
            a, b = a.astype(F8).astype(cd), b.astype(F8).astype(cd)
        return jnp.einsum(spec, a, b, preferred_element_type=jnp.float32,
                          precision=jax.lax.Precision.HIGHEST)

    p = frame.reshape(H // patch, patch, W // patch, patch)
    p = p.transpose(0, 2, 1, 3).reshape(seq, patch * patch)
    x = (mm("sp,pd->sd", p, params["embedder"]["proj"])
         + params["embedder"]["pos"]).astype(cd)
    bb = params["backbone"]
    causal = jnp.tril(jnp.ones((seq, seq), bool))

    def block(x, lp):
        a = _rms_norm(x, lp["attn_norm"]["scale"], cd)
        at = lp["attn"]
        q = _rope(mm("sd,dhk->shk", a, at["wq"]).astype(cd), theta)
        k = _rope(mm("sd,dhk->shk", a, at["wk"]).astype(cd), theta)
        v = mm("sd,dhk->shk", a, at["wv"]).astype(cd)
        group = q.shape[1] // k.shape[1]
        k, v = jnp.repeat(k, group, axis=1), jnp.repeat(v, group, axis=1)
        s = mm("qhk,shk->hqs", q, k) / np.float32(np.sqrt(q.shape[-1]))
        s = jnp.where(causal, s, -jnp.inf)
        o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v).astype(cd)
        x = x + mm("shk,hkd->sd", o, at["wo"]).astype(cd)
        m = _rms_norm(x, lp["mlp_norm"]["scale"], cd)
        gate = mm("sd,df->sf", m, lp["mlp"]["w_gate"])
        up = mm("sd,df->sf", m, lp["mlp"]["w_up"])
        x = x + mm("sf,fd->sd", jax.nn.silu(gate) * up,
                   lp["mlp"]["w_down"]).astype(cd)
        return x, None

    x, _ = jax.lax.scan(block, x, bb["layers"])
    x = _rms_norm(x, bb["final_norm"]["scale"], cd)
    logits = mm("sd,dv->sv", x, bb["unembed"]["kernel"]).astype(cd)
    return logits[-1, :n_out].astype(jnp.float32)


@functools.partial(jax.jit, static_argnames=("patch", "n_out", "theta",
                                             "mode"))
def _forward(params, frames, *, patch, n_out, theta, mode):
    cd, q8 = MODES[mode]
    f = functools.partial(_one_frame, patch=patch, n_out=n_out, theta=theta,
                          cd=cd, q8=q8)
    return jax.vmap(lambda fr: f(params, fr))(frames)


def logits(params, frames: np.ndarray, d: dict, *,
           mode: str = "bfloat16") -> np.ndarray:
    """``(M, H, W)`` high-precision frames -> ``(M, n_out)`` logits in
    ``mode`` (:data:`MODES`)."""
    frames = np.asarray(frames, np.float32)
    out = _forward(params, jnp.asarray(frames), patch=d["patch"],
                   n_out=d["n_out"], theta=float(d["rope_theta"]), mode=mode)
    return np.asarray(out, np.float32).reshape(frames.shape[0], d["n_out"])
