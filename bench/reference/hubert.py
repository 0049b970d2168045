"""Plain reference of the gated detector: hubert-xlarge over frame patches.

The encoder of HuBERT X-Large (arXiv:2106.07447, Table 1: 48 layers,
d=1280, 16 heads, FFN 5120; pre-layer-norm), as the cascade serves it:

- each ``H x W`` high-precision frame is cut into ``patch x patch``
  patches, projected to ``d_model`` and given a learned position
  embedding (this stands in for HuBERT's convolutional waveform front end);
- 48 pre-norm blocks: layer norm, bidirectional multi-head attention with
  rotary position embedding on queries and keys, residual; layer norm,
  GELU (tanh form) feed-forward, residual;
- a final layer norm and the output projection; the detector reads the
  first ``n_out`` outputs at the last position.

Every product runs in the compute dtype the configuration states:
bfloat16 operands, float32 accumulation; layer-norm statistics, softmax
and the rotary arithmetic stay in float32. Two more modes (:data:`MODES`)
serve the check: ``float32`` throughout, and ``float8``, which rounds
every product's operands to float8 (e4m3) first: the control, one
precision below the stated one.

The weights are made here from the seed, on the device, in one call, in
float32 (the type the cascade holds them in), in the tree layout the
cascade takes. The module keeps the detector references' contract
(``bench/reference/__init__.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np

F8 = jnp.float8_e4m3fn


def tokens(g: dict, d: dict) -> int:
    return (g["frame_h"] // d["patch"]) * (g["frame_w"] // d["patch"])


@functools.partial(jax.jit, static_argnames=("shapes",))
def _init(key, *, shapes):
    """Weights from ``key``: fan-in scaled normals, unit norms."""
    L, dm, nh, hd, f, vocab, pp, seq = shapes
    ks = jax.random.split(key, 9)

    def nrm(k, shape, fan_in):
        return jax.random.normal(k, shape, jnp.float32) * fan_in ** -0.5

    ln = lambda: {"scale": jnp.ones((L, dm), jnp.float32),
                  "bias": jnp.zeros((L, dm), jnp.float32)}
    return {
        "backbone": {
            "final_norm": {"scale": jnp.ones((dm,), jnp.float32),
                           "bias": jnp.zeros((dm,), jnp.float32)},
            "unembed": {"kernel": nrm(ks[0], (dm, vocab), dm)},
            "layers": {
                "attn_norm": ln(),
                "attn": {"wq": nrm(ks[1], (L, dm, nh, hd), dm),
                         "wk": nrm(ks[2], (L, dm, nh, hd), dm),
                         "wv": nrm(ks[3], (L, dm, nh, hd), dm),
                         "wo": nrm(ks[4], (L, nh, hd, dm), nh * hd)},
                "mlp_norm": ln(),
                "mlp": {"w_up": nrm(ks[5], (L, dm, f), dm),
                        "w_down": nrm(ks[6], (L, f, dm), f)},
            },
        },
        "embedder": {
            "proj": jax.random.normal(ks[7], (pp, dm), jnp.float32)
            / float(np.sqrt(pp)),
            "pos": 0.02 * jax.random.normal(ks[8], (seq, dm), jnp.float32),
        },
    }


def make_weights(key, g: dict, d: dict) -> dict:
    shapes = (d["n_layers"], d["d_model"], d["n_heads"],
              d["d_model"] // d["n_heads"], d["d_ff"], d["vocab"],
              d["patch"] * d["patch"], tokens(g, d))
    return _init(key, shapes=shapes)


def frame_flops(g: dict, d: dict) -> float:
    """Forward FLOPs of the encoder layers for one real frame.

    Per token and layer: Q, K, V and output projections (8 d^2), the
    feed-forward up and down projections (4 d d_ff), and the attention
    scores and weighted sum over ``s`` tokens (4 s d). With d_ff = 4 d this
    is the familiar 24 d^2 + 4 s d; for hubert-xlarge at 128x128 frames in
    8x8 patches, 256 tokens x 48 layers come to about 0.499 TFLOP.
    """
    s = tokens(g, d)
    dm, f = d["d_model"], d["d_ff"]
    per_token = 8 * dm * dm + 4 * dm * f + 4 * s * dm
    return float(s * d["n_layers"] * per_token)


def tiny(d: dict) -> dict:
    """The keys of ``d`` a CPU test shrinks: two layers of width 64."""
    return {"n_layers": 2, "d_model": 64, "n_heads": 4, "kv_heads": 4,
            "d_ff": 128, "vocab": 64, "batch": 2}


def _layer_norm(x, p, cd):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.mean(jnp.square(xf - mu), axis=-1, keepdims=True)
    inv = jax.lax.rsqrt(var + 1e-5).astype(cd)
    return (x - mu.astype(cd)) * inv * p["scale"].astype(cd) \
        + p["bias"].astype(cd)


def _rope(x, theta):
    """Rotary embedding on ``(s, heads, hd)``, halves not interleaved."""
    s, _, hd = x.shape
    freqs = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = jnp.arange(s, dtype=jnp.float32)[:, None] * freqs     # (s, hd/2)
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x1 * sin + x2 * cos], -1)


def _gelu(x):
    c = np.sqrt(2.0 / np.pi).astype(np.float32)
    return 0.5 * x * (1.0 + jnp.tanh(c * (x + 0.044715 * x * x * x)))


def _one_frame(params, frame, *, patch, n_out, theta, cd, q8):
    H, W = frame.shape
    seq = (H // patch) * (W // patch)

    def mm(spec, a, b):
        """A product on ``cd`` operands (float8-rounded under ``q8``),
        accumulated in float32."""
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

    def block(x, lp):
        a = _layer_norm(x, lp["attn_norm"], cd)
        q = _rope(mm("sd,dhk->shk", a, lp["attn"]["wq"]).astype(cd), theta)
        k = _rope(mm("sd,dhk->shk", a, lp["attn"]["wk"]).astype(cd), theta)
        v = mm("sd,dhk->shk", a, lp["attn"]["wv"]).astype(cd)
        s = mm("qhk,shk->hqs", q, k) / np.float32(np.sqrt(q.shape[-1]))
        o = mm("hqs,shk->qhk", jax.nn.softmax(s, axis=-1), v).astype(cd)
        x = x + mm("shk,hkd->sd", o, lp["attn"]["wo"]).astype(cd)
        m = _layer_norm(x, lp["mlp_norm"], cd)
        up = mm("sd,df->sf", m, lp["mlp"]["w_up"]).astype(cd)
        x = x + mm("sf,fd->sd", _gelu(up), lp["mlp"]["w_down"]).astype(cd)
        return x, None

    x, _ = jax.lax.scan(block, x, bb["layers"])
    x = _layer_norm(x, bb["final_norm"], cd)
    logits = mm("sd,dv->sv", x, bb["unembed"]["kernel"]).astype(cd)
    return logits[-1, :n_out].astype(jnp.float32)


#: the reference's modes: the configuration's bfloat16; float32 (how much
#: the network itself moves under bfloat16 rounding); the float8 control
MODES = {"bfloat16": (jnp.bfloat16, False), "float32": (jnp.float32, False),
         "float8": (jnp.bfloat16, True)}


@functools.partial(jax.jit, static_argnames=("patch", "n_out", "theta",
                                             "mode"))
def _forward(params, frames, *, patch, n_out, theta, mode):
    cd, q8 = MODES[mode]
    f = functools.partial(_one_frame, patch=patch, n_out=n_out, theta=theta,
                          cd=cd, q8=q8)
    return jax.vmap(lambda fr: f(params, fr))(frames)


def logits(params, frames: np.ndarray, d: dict, *, mode: str = "bfloat16",
           block: int = 8) -> np.ndarray:
    """``(M, H, W)`` high-precision frames -> ``(M, n_out)`` logits in
    ``mode`` (:data:`MODES`), ``block`` frames per call (the last block
    padded)."""
    frames = np.asarray(frames, np.float32)
    out = np.empty((frames.shape[0], d["n_out"]), np.float32)
    for lo in range(0, frames.shape[0], block):
        part = frames[lo:lo + block]
        m = part.shape[0]
        if m < block:
            part = np.concatenate(
                [part, np.zeros((block - m, *part.shape[1:]), np.float32)])
        r = _forward(params, jnp.asarray(part), patch=d["patch"],
                     n_out=d["n_out"], theta=float(d["rope_theta"]),
                     mode=mode)
        out[lo:lo + m] = np.asarray(r)[:m]
    return out
